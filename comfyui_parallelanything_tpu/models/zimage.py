"""Z-Image's single-stream diffusion transformer ("S3-DiT") — flax.linen, bf16.

The model the reference's own README benchmark is made on
(/root/reference/README.md:46-60). Published by Tongyi-MAI (Apache-2.0):
``transformer/config.json`` and ``src/zimage/transformer.py``. One stream, no
pooled vector, no shift in its modulation:

- the latent's 2 x 2 patches (features ordered patch row, patch column,
  channel) through ``x_embedder``; the text tower's states through
  ``cap_embedder`` (RMS norm, then a linear layer). Each stream is padded to
  the next multiple of ``SEQ_MULTI_OF`` (32) tokens with its LEARNED pad token
  (``x_pad_token`` / ``cap_pad_token``); the pad tokens are attended to;
- ``t_emb = Linear(SiLU(Linear(sincos_256(t · t_scale))))`` with
  ``t = 1 − σ``: the published model's time runs from noise (0) to image (1)
  and its output is the NEGATED velocity. This module takes the sampler's
  flow time σ and returns the velocity the sampler integrates
  (``prediction = "flow"``), so both conventions end here;
- three-axis rotary tables (θ 256, dims 32 / 48 / 48 of the 128-wide head,
  interleaved pairs) on q and k: caption token *i* sits at (1 + i, 0, 0), the
  image patch (h, w) at (L_cap_padded + 1, h, w), an image pad token at
  (0, 0, 0);
- a block: ``(scale_a, gate_a, scale_m, gate_m) = Linear(t_emb)`` (no SiLU
  before it), ``x += tanh(gate_a) · RMS(Attn(RMS(x) · (1 + scale_a)))``,
  ``x += tanh(gate_m) · RMS(W2(SiLU(W1 h) · W3 h))`` with
  ``h = RMS(x) · (1 + scale_m)`` — four RMS norms with learned scale a block,
  q/k RMS norm per head, no biases in the attention or the SwiGLU;
- ``noise_refiner`` blocks on the image tokens alone, ``context_refiner``
  blocks (unmodulated: scale 0, gate 1) on the caption tokens alone, then
  ``layers`` on image ⊕ caption tokens; the final layer
  ``Linear(LayerNorm(x_img) · (1 + Linear(SiLU(t_emb))))`` and unpatchify.

The caption arrives padded to its bucket by the text node (``context``
(B, L_bucket, cap_feat_dim)) with the count of valid tokens a row in ``y``
(B, 1): rows past the count are replaced by the pad token, so one compiled
program serves every caption of a bucket. ``pa_caption_bucket_total{tokens=}``
counts, once a trace, the padded caption length a program was compiled at.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax.numpy as jnp

from ..ops.attention import attention, qk_prologue
from ..ops.basic import timestep_embedding
from ..ops.rope import axis_rope_freqs
from .api import DiffusionModel
from .text_encoders import _RMSNorm


@dataclasses.dataclass(frozen=True)
class ZImageConfig:
    in_channels: int = 16          # latent channels; a token is patch² of them
    patch_size: int = 2
    dim: int = 3840
    n_layers: int = 30
    n_refiner_layers: int = 2
    n_heads: int = 30              # head dim 128; n_kv_heads is the same
    cap_feat_dim: int = 2560       # Qwen3-4B's hidden width
    axes_dims: tuple[int, ...] = (32, 48, 48)
    rope_theta: float = 256.0
    t_scale: float = 1000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # The sampler nodes read this: flow-time k-sampling (sampling/runner.py).
    prediction: str = "flow"

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def ffn_dim(self) -> int:
        return int(self.dim / 3 * 8)

    @property
    def adaln_embed_dim(self) -> int:
        """The width of ``t_emb``: ``min(dim, 256)`` as published."""
        return min(self.dim, 256)


# transformer.py's constants: each stream is padded to a multiple of
# SEQ_MULTI_OF tokens; the timestep embedder is sincos_256 → 1024 → t_emb.
SEQ_MULTI_OF = 32
T_FREQUENCY_DIM = 256
T_MID_DIM = 1024


def zimage_turbo_config(**overrides) -> ZImageConfig:
    """Tongyi-MAI/Z-Image-Turbo ``transformer/config.json``: 6.15 B parameters."""
    return dataclasses.replace(ZImageConfig(), **overrides)


def padded_length(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


class _Scale(nn.Module):
    """The learned ``scale`` of an RMS norm whose arithmetic runs elsewhere
    (the q/k prologue): the parameter under the name ``_RMSNorm`` gives it."""

    dim: int

    @nn.compact
    def __call__(self):
        return self.param("scale", nn.initializers.ones, (self.dim,))


class ZImageBlock(nn.Module):
    """One S3-DiT block; ``modulated`` False is the context refiner's."""

    cfg: ZImageConfig
    modulated: bool = True

    @nn.compact
    def __call__(self, x, rope, adaln=None):
        cfg = self.cfg
        B, S, _ = x.shape
        H, D = cfg.n_heads, cfg.head_dim

        def dense(width, name):
            return nn.Dense(width, use_bias=False, dtype=cfg.dtype, name=name)

        # Unmodulated (the context refiner): scale 0 and gate 1, nothing to do.
        scale_a = gate_a = scale_m = gate_m = None
        if self.modulated:
            mod = nn.Dense(4 * cfg.dim, dtype=jnp.float32, name="adaLN_modulation")(
                adaln.astype(jnp.float32))
            scale_a, gate_a, scale_m, gate_m = jnp.split(mod[:, None, :], 4, axis=-1)

        def scaled(h, scale):
            if scale is None:
                return h
            return (h.astype(jnp.float32) * (1.0 + scale)).astype(h.dtype)

        def gated(h, gate):
            if gate is None:
                return h
            return (jnp.tanh(gate) * h.astype(jnp.float32)).astype(h.dtype)

        h = scaled(_RMSNorm(cfg.norm_eps, name="attention_norm1")(x), scale_a)
        q, k, v = (dense(cfg.dim, n)(h).reshape(B, S, H, D)
                   for n in ("to_q", "to_k", "to_v"))
        q, k = qk_prologue(
            (q, k), _Scale(D, name="norm_q")(), _Scale(D, name="norm_k")(),
            cfg.norm_eps, rope)
        a = attention(q, k, v)
        a = dense(cfg.dim, "to_out")(a.reshape(B, S, cfg.dim))
        a = _RMSNorm(cfg.norm_eps, name="attention_norm2")(a)
        x = x + gated(a, gate_a)

        h = scaled(_RMSNorm(cfg.norm_eps, name="ffn_norm1")(x), scale_m)
        f = dense(cfg.dim, "w2")(nn.silu(dense(cfg.ffn_dim, "w1")(h))
                                 * dense(cfg.ffn_dim, "w3")(h))
        f = _RMSNorm(cfg.norm_eps, name="ffn_norm2")(f)
        return x + gated(f, gate_m)


class ZImageModel(nn.Module):
    """forward(x latent NHWC, timesteps σ (B,), context (B, L, cap_feat_dim)
    padded to a multiple of ``SEQ_MULTI_OF`` or not, y (B, 1) valid caption
    tokens a row or None: all) → the velocity the flow samplers integrate."""

    cfg: ZImageConfig

    @nn.compact
    def __call__(self, x, timesteps, context=None, y=None, **kwargs):
        cfg = self.cfg
        if context is None:
            raise ValueError("Z-Image requires the text tower's states as context")
        B, Hh, Ww, C = x.shape
        p, m = cfg.patch_size, SEQ_MULTI_OF
        hp, wp = Hh // p, Ww // p
        n_img, n_img_pad = hp * wp, padded_length(hp * wp, m)
        n_cap, n_cap_pad = context.shape[1], padded_length(context.shape[1], m)
        from ..utils.metrics import registry

        # Once a trace, as ops/attention counts its routes: which padded
        # caption lengths this process holds a step program for.
        registry.counter(
            "pa_caption_bucket_total", labels={"tokens": str(n_cap_pad)},
            help="denoiser traces by the padded caption length they compiled "
                 "at (models/zimage.py); a second value moving is a compile",
        )

        t = 1.0 - jnp.asarray(timesteps, jnp.float32)
        temb = timestep_embedding(t, T_FREQUENCY_DIM, time_factor=cfg.t_scale)
        temb = nn.Dense(T_MID_DIM, dtype=jnp.float32, name="t_embedder_0")(temb)
        adaln = nn.Dense(cfg.adaln_embed_dim, dtype=jnp.float32,
                         name="t_embedder_2")(nn.silu(temb))

        def pad_with(tokens, valid, n_pad, name):
            """Rows at or past ``valid`` (a count a row, or a static length)
            become the learned pad token, out to ``n_pad`` rows."""
            token = self.param(name, nn.initializers.normal(0.02), (cfg.dim,))
            if isinstance(valid, int) and valid == n_pad:
                return tokens  # nothing to pad: the parameter is the file's all the same
            tokens = jnp.pad(tokens, ((0, 0), (0, n_pad - tokens.shape[1]), (0, 0)))
            keep = jnp.arange(n_pad)[None, :, None] < valid
            return jnp.where(keep, tokens, token.astype(tokens.dtype))

        img = x.astype(cfg.dtype).reshape(B, hp, p, wp, p, C)
        img = img.transpose(0, 1, 3, 2, 4, 5).reshape(B, n_img, p * p * C)
        img = nn.Dense(cfg.dim, dtype=cfg.dtype, name="x_embedder")(img)
        img = pad_with(img, n_img, n_img_pad, "x_pad_token")

        cap = _RMSNorm(cfg.norm_eps, name="cap_embedder_0")(context.astype(cfg.dtype))
        cap = nn.Dense(cfg.dim, dtype=cfg.dtype, name="cap_embedder_1")(cap)
        valid = (n_cap if y is None
                 else jnp.asarray(y, jnp.float32).reshape(B, 1, 1))
        cap = pad_with(cap, valid, n_cap_pad, "cap_pad_token")

        # Position ids: caption (1 + i, 0, 0); image (L_cap_padded + 1, h, w),
        # its pad tokens (0, 0, 0).
        zeros = jnp.zeros((n_cap_pad,), jnp.int32)
        cap_ids = jnp.stack([1 + jnp.arange(n_cap_pad, dtype=jnp.int32), zeros, zeros], -1)
        hh, ww = jnp.meshgrid(jnp.arange(hp, dtype=jnp.int32),
                              jnp.arange(wp, dtype=jnp.int32), indexing="ij")
        img_ids = jnp.stack([jnp.full((hp, wp), n_cap_pad + 1, jnp.int32), hh, ww],
                            -1).reshape(n_img, 3)
        img_ids = jnp.pad(img_ids, ((0, n_img_pad - n_img), (0, 0)))

        def rope(ids):
            cos, sin = axis_rope_freqs(ids[None], cfg.axes_dims, cfg.rope_theta)
            return (jnp.broadcast_to(cos, (B, *cos.shape[1:])),
                    jnp.broadcast_to(sin, (B, *sin.shape[1:])))

        img_rope, cap_rope = rope(img_ids), rope(cap_ids)
        for i in range(cfg.n_refiner_layers):
            img = ZImageBlock(cfg, name=f"noise_refiner_{i}")(img, img_rope, adaln)
        for i in range(cfg.n_refiner_layers):
            cap = ZImageBlock(cfg, modulated=False,
                              name=f"context_refiner_{i}")(cap, cap_rope)
        seq = jnp.concatenate([img, cap], axis=1)
        seq_rope = rope(jnp.concatenate([img_ids, cap_ids], axis=0))
        for i in range(cfg.n_layers):
            seq = ZImageBlock(cfg, name=f"layers_{i}")(seq, seq_rope, adaln)

        scale = nn.Dense(cfg.dim, dtype=jnp.float32, name="final_mod")(nn.silu(adaln))
        out = nn.LayerNorm(use_bias=False, use_scale=False, epsilon=1e-6,
                           dtype=cfg.dtype, name="final_norm")(seq[:, :n_img])
        out = out.astype(jnp.float32) * (1.0 + scale[:, None, :])
        out = nn.Dense(p * p * C, dtype=jnp.float32, name="final_proj")(out)
        out = out.reshape(B, hp, wp, p, p, C).transpose(0, 1, 3, 2, 4, 5)
        # The published model's output is the negated velocity.
        return -out.reshape(B, Hh, Ww, C)


def build_zimage(cfg: ZImageConfig, rng=None, sample_shape=(1, 16, 16, 16),
                 txt_len=32, name="zimage-turbo", params=None) -> DiffusionModel:
    """Build a Z-Image DiffusionModel; ``params`` skips initialization (the
    checkpoint-load path)."""
    module = ZImageModel(cfg)
    if params is None:
        if rng is None:
            raise ValueError("need rng to initialize (or pass params=)")
        x = jnp.zeros(sample_shape, jnp.float32)
        t = jnp.zeros((sample_shape[0],), jnp.float32)
        ctx = jnp.zeros((sample_shape[0], txt_len, cfg.cap_feat_dim), jnp.float32)
        params = module.init(rng, x, t, ctx)["params"]

    def apply(params, x, timesteps, context=None, **kw):
        return module.apply({"params": params}, x, timesteps, context, **kw)

    return DiffusionModel(
        apply=apply, params=params, name=name, config=cfg,
        block_lists={"noise_refiner": cfg.n_refiner_layers,
                     "context_refiner": cfg.n_refiner_layers,
                     "layers": cfg.n_layers},
    )
