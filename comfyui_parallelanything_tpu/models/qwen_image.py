"""Qwen-Image's double-stream diffusion transformer — flax.linen, bf16.

Published by Qwen (Apache-2.0): ``transformer/config.json`` of Qwen/Qwen-Image
and ``transformer_qwenimage.py`` of diffusers. 60 blocks, every one FLUX's
double-stream block to the letter (``models/flux.DoubleBlock``, used as it
is: each stream its own adaLN modulation of (shift, scale, gate) x 2, q / k / v
with bias, per-head RMS norm of q and k, ONE attention over text ⊕ image with
the interleaved rotary, gated projection, gated tanh-GELU MLP) and NO
single-stream tail. What differs from FLUX stands around the blocks:

- the modulation vector is the timestep's alone (``Linear(SiLU(Linear(
  sincos_256(1000 σ))))``): no pooled vector, no guidance;
- the text enters through an RMS norm with a learned scale and a linear
  layer, 3584 → 3072: the Qwen2.5-VL tower's states of the VALID tokens and
  nothing else — 10 to 20 rows against 6,889 image tokens at 1328², so a
  text's length is part of the step program's shape and
  ``pa_caption_bucket_total{tokens=}`` counts, once a trace, which lengths
  this process holds a program for;
- rotary positions are CENTRED on the image (``scale_rope``): the patch at
  row i, column j of an h x w grid sits at (0, i − (h − ⌊h/2⌋), j − (w −
  ⌊w/2⌋)), text token n at (p, p, p) with p = max(⌊h/2⌋, ⌊w/2⌋) + n;
- the head's modulation is (scale, shift), scale first.

The output is the velocity the flow samplers integrate (``prediction =
"flow"``), no sign change. The forward is staged (``prepare`` /
``block_step`` / ``finalize``) as FLUX's is, so that the batch-1 pipeline
placement can give each device a contiguous block range: 8 + 8 + 8 + 8 + 7 + 7
+ 7 + 7 over a v5e-8 host is the deployment the benchmark's cut stands for.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax.numpy as jnp

from ..ops.basic import modulate as _modulate, timestep_embedding
from ..ops.rope import axis_rope_freqs
from .api import DiffusionModel, PipelineSegment, PipelineSpec
from .flux import DoubleBlock, MLPEmbedder
from .text_encoders import _RMSNorm


@dataclasses.dataclass(frozen=True)
class QwenImageConfig:
    in_channels: int = 64          # 16 latent channels x 2 x 2 patch
    hidden_size: int = 3072        # num_attention_heads x attention_head_dim
    num_heads: int = 24            # head dim 128
    depth: int = 60                # num_layers
    mlp_ratio: float = 4.0
    joint_attention_dim: int = 3584   # Qwen2.5-VL-7B's hidden width
    axes_dim: tuple[int, ...] = (16, 56, 56)
    theta: float = 10000.0
    patch_size: int = 2
    dtype: Any = jnp.bfloat16
    # The sampler nodes read this: flow-time k-sampling (sampling/runner.py).
    prediction: str = "flow"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


def qwen_image_config(**overrides) -> QwenImageConfig:
    """Qwen/Qwen-Image ``transformer/config.json``: 20.43 B parameters."""
    return dataclasses.replace(QwenImageConfig(), **overrides)


def centred_position_ids(hp: int, wp: int, txt_len: int):
    """(txt_len + hp·wp, 3) int32, text first: ``QwenEmbedRope`` with
    ``scale_rope`` — the image grid centred on 0 along rows and columns, the
    text after the grid's half-extent on all three axes."""
    rows = jnp.arange(hp, dtype=jnp.int32) - (hp - hp // 2)
    cols = jnp.arange(wp, dtype=jnp.int32) - (wp - wp // 2)
    img = jnp.stack([jnp.zeros((hp, wp), jnp.int32),
                     jnp.broadcast_to(rows[:, None], (hp, wp)),
                     jnp.broadcast_to(cols[None, :], (hp, wp))], -1).reshape(hp * wp, 3)
    txt = max(hp // 2, wp // 2) + jnp.arange(txt_len, dtype=jnp.int32)
    return jnp.concatenate([jnp.broadcast_to(txt[:, None], (txt_len, 3)), img])


class QwenImageModel(nn.Module):
    """forward(x latent NHWC, timesteps σ (B,), context (B, L, 3584): the
    tower's states of the valid tokens) → the velocity. The carry between
    stages is FLUX's: img, txt, vec, rope_cos, rope_sin."""

    cfg: QwenImageConfig

    def setup(self):
        cfg = self.cfg
        self.img_in = nn.Dense(cfg.hidden_size, dtype=cfg.dtype)
        self.txt_norm = _RMSNorm(1e-6)
        self.txt_in = nn.Dense(cfg.hidden_size, dtype=cfg.dtype)
        self.time_in = MLPEmbedder(cfg)
        self.transformer_blocks = [DoubleBlock(cfg) for _ in range(cfg.depth)]
        self.final_mod = nn.Dense(2 * cfg.hidden_size, dtype=jnp.float32)
        self.final_norm = nn.LayerNorm(use_bias=False, use_scale=False, dtype=cfg.dtype)
        self.final_proj = nn.Dense(cfg.in_channels, dtype=jnp.float32)

    def prepare(self, x, timesteps, context=None, **kwargs):
        cfg = self.cfg
        if context is None:
            raise ValueError("Qwen-Image requires the text tower's states as context")
        B, Hh, Ww, C = x.shape
        p = cfg.patch_size
        hp, wp = Hh // p, Ww // p
        txt_len = context.shape[1]
        from ..utils.metrics import registry

        # Once a trace, as models/zimage.py counts its buckets: which text
        # lengths this process holds a step program for.
        registry.counter(
            "pa_caption_bucket_total", labels={"tokens": str(txt_len)},
            help="denoiser traces by the padded caption length they compiled "
                 "at (models/zimage.py); a second value moving is a compile",
        )

        img = x.astype(cfg.dtype).reshape(B, hp, p, wp, p, C)
        img = img.transpose(0, 1, 3, 2, 4, 5).reshape(B, hp * wp, p * p * C)
        img = self.img_in(img)
        txt = self.txt_in(self.txt_norm(context.astype(cfg.dtype)))
        vec = self.time_in(
            timestep_embedding(timesteps, 256, time_factor=1000.0).astype(cfg.dtype))
        ids = centred_position_ids(hp, wp, txt_len)
        cos, sin = axis_rope_freqs(
            jnp.broadcast_to(ids, (B, *ids.shape)), cfg.axes_dim, cfg.theta)
        return {"img": img, "txt": txt, "vec": vec, "rope_cos": cos, "rope_sin": sin}

    def block_step(self, carry, i: int):
        img, txt = self.transformer_blocks[i](
            carry["img"], carry["txt"], carry["vec"],
            (carry["rope_cos"], carry["rope_sin"]))
        return {**carry, "img": img, "txt": txt}

    def finalize(self, carry, out_shape: tuple[int, ...]):
        cfg = self.cfg
        B, Hh, Ww, C = out_shape
        p = cfg.patch_size
        hp, wp = Hh // p, Ww // p
        # AdaLayerNormContinuous: the linear's two halves are (scale, shift).
        scale, shift = jnp.split(
            self.final_mod(nn.silu(carry["vec"].astype(jnp.float32)))[:, None, :],
            2, axis=-1)
        img = _modulate(self.final_norm(carry["img"]), shift, scale)
        img = self.final_proj(img.astype(jnp.float32))
        img = img.reshape(B, hp, wp, p, p, C).transpose(0, 1, 3, 2, 4, 5)
        return img.reshape(B, Hh, Ww, C)

    def __call__(self, x, timesteps, context=None, **kwargs):
        carry = self.prepare(x, timesteps, context)
        for i in range(self.cfg.depth):
            carry = self.block_step(carry, i)
        return self.finalize(carry, x.shape)


def _pipeline_spec(module: QwenImageModel, cfg: QwenImageConfig) -> PipelineSpec:
    """Embedders on the lead device, one segment a block, the head on the
    lead: ``models/flux._flux_pipeline_spec`` without the single-stream tail."""

    def prepare(params, x, t, context=None, **kw):
        return module.apply({"params": params}, x, t, context,
                            method=QwenImageModel.prepare, **kw)

    def make_block(i):
        def fn(params, carry):
            return module.apply({"params": params}, carry, i,
                                method=QwenImageModel.block_step)

        return fn

    def finalize(params, carry, out_shape):
        return module.apply({"params": params}, carry, out_shape,
                            method=QwenImageModel.finalize)

    return PipelineSpec(
        prepare_keys=("img_in", "txt_norm", "txt_in", "time_in"),
        prepare=prepare,
        segments=tuple(
            PipelineSegment((f"transformer_blocks_{i}",), make_block(i),
                            f"transformer_blocks[{i}]")
            for i in range(cfg.depth)),
        finalize_keys=("final_mod", "final_proj"),
        finalize=finalize,
    )


def build_qwen_image(cfg: QwenImageConfig, rng=None, sample_shape=(1, 16, 16, 16),
                     txt_len=8, name="qwen-image", params=None) -> DiffusionModel:
    """Build a Qwen-Image DiffusionModel; ``params`` skips initialization (the
    checkpoint-load path)."""
    module = QwenImageModel(cfg)
    if params is None:
        if rng is None:
            raise ValueError("need rng to initialize (or pass params=)")
        x = jnp.zeros(sample_shape, jnp.float32)
        t = jnp.zeros((sample_shape[0],), jnp.float32)
        ctx = jnp.zeros((sample_shape[0], txt_len, cfg.joint_attention_dim), jnp.float32)
        params = module.init(rng, x, t, ctx)["params"]

    def apply(params, x, timesteps, context=None, **kw):
        return module.apply({"params": params}, x, timesteps, context, **kw)

    return DiffusionModel(
        apply=apply, params=params, name=name, config=cfg,
        block_lists={"transformer_blocks": cfg.depth},
        pipeline_spec=_pipeline_spec(module, cfg),
    )
