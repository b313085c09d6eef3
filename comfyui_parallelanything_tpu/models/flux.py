"""FLUX-class MMDiT — flax.linen, bf16, TPU-first. The flagship model family.

Capability target: FLUX.1 is one of the reference's headline workloads
(/root/reference/README.md:5), and its pipeline mode walks exactly the block
lists this model exposes — ``double_blocks`` then ``single_blocks``
(any_device_parallel.py:1156). The config knobs mirror the ctor kwargs the reference
scrapes off live FLUX models when cloning: ``vec_in_dim``, ``context_in_dim``,
``depth``, ``depth_single_blocks``, ``axes_dim``, ``theta``, ``guidance_embed``
(any_device_parallel.py:286-296). Fresh TPU implementation — joint attention through
the pluggable backend (pallas flash qualifies: head_dim 128), f32 modulation/softmax,
bf16 matmuls.

Architecture (public FLUX.1 recipe): latent 2×2-patchified to 64-ch tokens; text
tokens projected from T5 features; (timestep, pooled-clip, guidance) → modulation
vector; `depth` double-stream blocks (separate img/txt weights, joint attention);
`depth_single_blocks` fused-stream blocks; adaLN-modulated final projection.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import attention, qk_prologue
from ..ops.basic import modulate as _modulate, timestep_embedding
from ..ops.rope import axis_rope_freqs
from .api import DiffusionModel, PipelineSegment, PipelineSpec


@dataclasses.dataclass(frozen=True)
class FluxConfig:
    in_channels: int = 64          # 16 latent ch × 2×2 patch
    hidden_size: int = 3072
    num_heads: int = 24            # head_dim 128
    depth: int = 19                # double blocks
    depth_single_blocks: int = 38
    mlp_ratio: float = 4.0
    context_in_dim: int = 4096     # T5 features
    vec_in_dim: int = 768          # pooled CLIP
    axes_dim: tuple[int, ...] = (16, 56, 56)
    theta: float = 10000.0
    guidance_embed: bool = True
    patch_size: int = 2
    dtype: Any = jnp.bfloat16
    # Rectified-flow velocity parameterization: the KSampler node reads this to
    # route flux-family models through flow-time k-sampling (sampling/runner.py)
    # instead of the eps sigma table.
    prediction: str = "flow"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


def flux_dev_config(**overrides) -> FluxConfig:
    return dataclasses.replace(FluxConfig(), **overrides)


def flux_schnell_config(**overrides) -> FluxConfig:
    return dataclasses.replace(FluxConfig(guidance_embed=False), **overrides)


def flux_single_heavy_config(**overrides) -> FluxConfig:
    """A FLUX-class MMDiT at 6 double + 26 single blocks, no guidance
    embedder: the single-stream-heavy point of THIS family at FLUX's widths,
    5.8 B parameters. No published model has this shape; ``bench.py``'s
    ``flux_single_heavy_21`` rungs time it at batch 21. (Until PR 34 it
    carried Z-Image's name as a guess at an architecture that was not public
    then; the published one is ``models/zimage.py`` and is nothing like it.)"""
    base = FluxConfig(
        depth=6,
        depth_single_blocks=26,
        guidance_embed=False,
    )
    return dataclasses.replace(base, **overrides)


class MLPEmbedder(nn.Module):
    cfg: FluxConfig

    @nn.compact
    def __call__(self, x):
        h = nn.Dense(self.cfg.hidden_size, dtype=self.cfg.dtype, name="in_layer")(x)
        return nn.Dense(self.cfg.hidden_size, dtype=self.cfg.dtype, name="out_layer")(
            nn.silu(h)
        )


class Modulation(nn.Module):
    """vec → (shift, scale, gate) × n sets, computed in f32 for stability."""

    cfg: FluxConfig
    n_sets: int

    @nn.compact
    def __call__(self, vec):
        out = nn.Dense(3 * self.n_sets * self.cfg.hidden_size, dtype=jnp.float32, name="lin")(
            nn.silu(vec.astype(jnp.float32))
        )
        return jnp.split(out[:, None, :], 3 * self.n_sets, axis=-1)


class FusedQKV(nn.Module):
    """``nn.DenseGeneral((3, H, D))`` — the same parameters (``kernel``
    (in, 3, H, D), ``bias`` (3, H, D)), the same initialisation, the same
    numbers — computed as ONE flat matmul that writes (B, S, 3·H·D): q, k and
    v as column blocks of H·D, the layout the q/k prologue and the flash
    kernel read. A dot with several feature dims is laid out sequence-minor
    by the TPU compiler and relaid for every consumer (a ``copy`` of the whole
    output and a separate pass for the bias; ISSUE 35)."""

    heads: int
    head_dim: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        features = (3, self.heads, self.head_dim)

        def flat_draw(rng, shape, dtype=jnp.float32):
            # DenseGeneral's: the kernel is drawn at its flat (in, 3·H·D) shape.
            flat = (shape[0], math.prod(features))
            return nn.linear.default_kernel_init(rng, flat, dtype).reshape(shape)

        kernel = self.param("kernel", flat_draw, (x.shape[-1], *features))
        bias = self.param("bias", nn.initializers.zeros_init(), features)
        x, kernel, bias = nn.dtypes.promote_dtype(x, kernel, bias, dtype=self.dtype)
        return x @ kernel.reshape(x.shape[-1], -1) + bias.reshape(-1)


class QKNorm(nn.Module):
    """Per-head RMSNorm on q and k (f32), FLUX-style, then the rotary: the
    q/k prologue (``ops/attention.qk_prologue``) with this module's scales.
    ``qkv`` is (B, S, N, H, D) with q and k its first two of N; ``rope`` the
    (cos, sin) of its rows."""

    @nn.compact
    def __call__(self, qkv, rope):
        dim = qkv.shape[-1]
        return qk_prologue(
            qkv, self.param("query_norm", nn.initializers.ones, (dim,)),
            self.param("key_norm", nn.initializers.ones, (dim,)), rope=rope)


class DoubleBlock(nn.Module):
    """Separate img/txt streams; one joint attention over [txt ‖ img] tokens."""

    cfg: FluxConfig

    @nn.compact
    def __call__(self, img, txt, vec, rope):
        cfg = self.cfg
        H, D = cfg.num_heads, cfg.head_dim
        mlp_dim = int(cfg.hidden_size * cfg.mlp_ratio)

        im_shift1, im_scale1, im_gate1, im_shift2, im_scale2, im_gate2 = Modulation(
            cfg, 2, name="img_mod"
        )(vec)
        tx_shift1, tx_scale1, tx_gate1, tx_shift2, tx_scale2, tx_gate2 = Modulation(
            cfg, 2, name="txt_mod"
        )(vec)

        cos, sin = rope
        txt_len = txt.shape[1]

        def qkv(x, name, rows):
            # Each stream is normalised with its own scales and rotated with
            # its rows of the table, before the two are joined.
            h = FusedQKV(H, D, cfg.dtype, name=f"{name}_qkv")(x)
            q, k = QKNorm(name=f"{name}_norm")(
                h.reshape(*x.shape[:2], 3, H, D), (cos[:, rows], sin[:, rows]))
            return q, k, h[..., 2 * H * D:].reshape(*x.shape[:2], H, D)

        img_n = _modulate(nn.LayerNorm(use_bias=False, use_scale=False, dtype=cfg.dtype,
                                       name="img_norm1")(img), im_shift1, im_scale1)
        txt_n = _modulate(nn.LayerNorm(use_bias=False, use_scale=False, dtype=cfg.dtype,
                                       name="txt_norm1")(txt), tx_shift1, tx_scale1)
        iq, ik, iv = qkv(img_n, "img_attn", slice(txt_len, None))
        tq, tk, tv = qkv(txt_n, "txt_attn", slice(None, txt_len))

        q = jnp.concatenate([tq, iq], axis=1)
        k = jnp.concatenate([tk, ik], axis=1)
        v = jnp.concatenate([tv, iv], axis=1)
        attn = attention(q, k, v)
        attn = attn.reshape(attn.shape[0], attn.shape[1], -1)
        txt_attn, img_attn = attn[:, :txt_len], attn[:, txt_len:]

        img = img + im_gate1.astype(cfg.dtype) * nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="img_attn_proj")(img_attn)
        txt = txt + tx_gate1.astype(cfg.dtype) * nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="txt_attn_proj")(txt_attn)

        img_m = _modulate(nn.LayerNorm(use_bias=False, use_scale=False, dtype=cfg.dtype,
                                       name="img_norm2")(img), im_shift2, im_scale2)
        txt_m = _modulate(nn.LayerNorm(use_bias=False, use_scale=False, dtype=cfg.dtype,
                                       name="txt_norm2")(txt), tx_shift2, tx_scale2)
        img = img + im_gate2.astype(cfg.dtype) * nn.Sequential([
            nn.Dense(mlp_dim, dtype=cfg.dtype, name="img_mlp_in"),
            nn.gelu,
            nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="img_mlp_out"),
        ])(img_m)
        txt = txt + tx_gate2.astype(cfg.dtype) * nn.Sequential([
            nn.Dense(mlp_dim, dtype=cfg.dtype, name="txt_mlp_in"),
            nn.gelu,
            nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="txt_mlp_out"),
        ])(txt_m)
        return img, txt


class SingleBlock(nn.Module):
    """Fused stream: one linear makes qkv + mlp_in together, one linear closes."""

    cfg: FluxConfig

    @nn.compact
    def __call__(self, x, vec, rope):
        cfg = self.cfg
        H, D = cfg.num_heads, cfg.head_dim
        mlp_dim = int(cfg.hidden_size * cfg.mlp_ratio)
        shift, scale, gate = Modulation(cfg, 1, name="modulation")(vec)

        x_n = _modulate(nn.LayerNorm(use_bias=False, use_scale=False, dtype=cfg.dtype,
                                     name="pre_norm")(x), shift, scale)
        fused = nn.Dense(3 * cfg.hidden_size + mlp_dim, dtype=cfg.dtype, name="linear1")(x_n)
        qkv, mlp = fused[..., : 3 * cfg.hidden_size], fused[..., 3 * cfg.hidden_size :]
        v = qkv[..., 2 * cfg.hidden_size:].reshape(x.shape[0], x.shape[1], H, D)
        # q and k are read from ``linear1``'s output where they lie: its
        # column blocks of H·D, where the MLP's width is whole ones too.
        src = qkv if mlp_dim % cfg.hidden_size else fused
        q, k = QKNorm(name="norm")(
            src.reshape(x.shape[0], x.shape[1], -1, H, D), rope)
        attn = attention(q, k, v).reshape(x.shape[0], x.shape[1], -1)
        out = nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="linear2")(
            jnp.concatenate([attn, nn.gelu(mlp)], axis=-1)
        )
        return x + gate.astype(cfg.dtype) * out


class FluxModel(nn.Module):
    """forward(x latent NHWC, timesteps (B,), context (B,S,ctx_dim),
    y=(B,vec_dim) pooled vector, guidance=(B,) optional).

    Setup-style (not @nn.compact) so the forward decomposes into staged methods —
    ``prepare`` / ``double_step`` / ``single_step`` / ``finalize`` — callable
    individually via ``module.apply(..., method=...)`` with only the parameter
    sub-pytree each stage owns. That is what makes the batch==1 pipeline placement
    mode (reference: block-list walk, any_device_parallel.py:1152-1198) expressible
    as per-device jit programs instead of monkey-patched module wrappers. The carry
    between stages is a flat dict of arrays: img, txt, vec, rope_cos, rope_sin.
    """

    cfg: FluxConfig

    def setup(self):
        cfg = self.cfg
        self.img_in = nn.Dense(cfg.hidden_size, dtype=cfg.dtype)
        self.txt_in = nn.Dense(cfg.hidden_size, dtype=cfg.dtype)
        self.time_in = MLPEmbedder(cfg)
        if cfg.guidance_embed:
            self.guidance_in = MLPEmbedder(cfg)
        self.vector_in = MLPEmbedder(cfg)
        self.double_blocks = [DoubleBlock(cfg) for _ in range(cfg.depth)]
        self.single_blocks = [SingleBlock(cfg) for _ in range(cfg.depth_single_blocks)]
        self.final_mod = nn.Dense(2 * cfg.hidden_size, dtype=jnp.float32)
        self.final_norm = nn.LayerNorm(use_bias=False, use_scale=False, dtype=cfg.dtype)
        # in_channels is already the patchified token width (p*p*latent_ch), so the
        # projection back to patches has exactly in_channels features.
        self.final_proj = nn.Dense(cfg.in_channels, dtype=jnp.float32)

    def prepare(self, x, timesteps, context=None, y=None, guidance=None, **kwargs):
        """Embeddings + position tables → the stage carry (runs on the lead device)."""
        cfg = self.cfg
        B, Hh, Ww, C = x.shape
        p = cfg.patch_size
        hp, wp = Hh // p, Ww // p

        # 2×2 patchify → (B, hp*wp, in_channels)
        img = x.astype(cfg.dtype).reshape(B, hp, p, wp, p, C)
        img = img.transpose(0, 1, 3, 2, 4, 5).reshape(B, hp * wp, p * p * C)
        img = self.img_in(img)

        if context is None:
            raise ValueError("FLUX requires text context tokens")
        txt = self.txt_in(context.astype(cfg.dtype))

        vec = self.time_in(
            timestep_embedding(timesteps, 256, time_factor=1000.0).astype(cfg.dtype)
        )
        if cfg.guidance_embed:
            if guidance is None:
                guidance = jnp.full((B,), 4.0, jnp.float32)
            vec = vec + self.guidance_in(
                timestep_embedding(guidance, 256, time_factor=1000.0).astype(cfg.dtype)
            )
        if y is None:
            y = jnp.zeros((B, cfg.vec_in_dim), jnp.float32)
        vec = vec + self.vector_in(y.astype(cfg.dtype))

        # Position ids: txt tokens at axis-0 index 0, img tokens on the (h, w) grid.
        txt_len = txt.shape[1]
        txt_ids = jnp.zeros((B, txt_len, 3), jnp.int32)
        hh = jnp.arange(hp, dtype=jnp.int32)
        ww = jnp.arange(wp, dtype=jnp.int32)
        grid = jnp.stack(
            [
                jnp.zeros((hp, wp), jnp.int32),
                jnp.broadcast_to(hh[:, None], (hp, wp)),
                jnp.broadcast_to(ww[None, :], (hp, wp)),
            ],
            axis=-1,
        ).reshape(1, hp * wp, 3)
        img_ids = jnp.broadcast_to(grid, (B, hp * wp, 3))
        ids = jnp.concatenate([txt_ids, img_ids], axis=1)
        cos, sin = axis_rope_freqs(ids, cfg.axes_dim, cfg.theta)
        return {"img": img, "txt": txt, "vec": vec, "rope_cos": cos, "rope_sin": sin}

    def double_step(self, carry, i: int):
        img, txt = self.double_blocks[i](
            carry["img"], carry["txt"], carry["vec"], (carry["rope_cos"], carry["rope_sin"])
        )
        return {**carry, "img": img, "txt": txt}

    def single_step(self, carry, i: int):
        # Single blocks run on the fused [txt ‖ img] stream; the carry keeps the two
        # streams separate (uniform structure across every segment) and fuses/splits
        # at the block boundary — XLA folds the concat/slice into the block program.
        txt_len = carry["txt"].shape[1]
        x = jnp.concatenate([carry["txt"], carry["img"]], axis=1)
        x = self.single_blocks[i](x, carry["vec"], (carry["rope_cos"], carry["rope_sin"]))
        return {**carry, "txt": x[:, :txt_len], "img": x[:, txt_len:]}

    def finalize(self, carry, out_shape: tuple[int, ...]):
        """Final adaLN + projection back to NHWC patches (runs on the lead device)."""
        cfg = self.cfg
        img, vec = carry["img"], carry["vec"]
        B, Hh, Ww, C = out_shape
        p = cfg.patch_size
        hp, wp = Hh // p, Ww // p
        shift, scale = jnp.split(
            self.final_mod(nn.silu(vec.astype(jnp.float32)))[:, None, :], 2, axis=-1
        )
        img = _modulate(self.final_norm(img), shift, scale)
        img = self.final_proj(img.astype(jnp.float32))
        img = img.reshape(B, hp, wp, p, p, C).transpose(0, 1, 3, 2, 4, 5)
        return img.reshape(B, Hh, Ww, C)

    def __call__(self, x, timesteps, context=None, y=None, guidance=None, **kwargs):
        carry = self.prepare(x, timesteps, context, y=y, guidance=guidance)
        for i in range(self.cfg.depth):
            carry = self.double_step(carry, i)
        for i in range(self.cfg.depth_single_blocks):
            carry = self.single_step(carry, i)
        return self.finalize(carry, x.shape)


def _flux_pipeline_spec(module: FluxModel, cfg: FluxConfig) -> PipelineSpec:
    """Stage decomposition mirroring the reference's block-list walk order
    (double_blocks then single_blocks, any_device_parallel.py:1156): embeddings on
    the lead device, one segment per block, final adaLN/projection on the lead."""

    def prepare(params, x, t, context=None, **kw):
        return module.apply(
            {"params": params}, x, t, context, method=FluxModel.prepare, **kw
        )

    def make_double(i):
        def fn(params, carry):
            return module.apply(
                {"params": params}, carry, i, method=FluxModel.double_step
            )

        return fn

    def make_single(i):
        def fn(params, carry):
            return module.apply(
                {"params": params}, carry, i, method=FluxModel.single_step
            )

        return fn

    def finalize(params, carry, out_shape):
        return module.apply(
            {"params": params}, carry, out_shape, method=FluxModel.finalize
        )

    segments = tuple(
        PipelineSegment((f"double_blocks_{i}",), make_double(i), f"double_blocks[{i}]")
        for i in range(cfg.depth)
    ) + tuple(
        PipelineSegment((f"single_blocks_{i}",), make_single(i), f"single_blocks[{i}]")
        for i in range(cfg.depth_single_blocks)
    )
    prepare_keys = ["img_in", "txt_in", "time_in", "vector_in"]
    if cfg.guidance_embed:
        prepare_keys.append("guidance_in")
    return PipelineSpec(
        prepare_keys=tuple(prepare_keys),
        prepare=prepare,
        segments=segments,
        # final_norm is scale/bias-free (no params) — only parameterized modules
        # appear in the param pytree.
        finalize_keys=("final_mod", "final_proj"),
        finalize=finalize,
    )


def flux_abstract_params(cfg: FluxConfig, sample_shape=(1, 32, 32, 16), txt_len=128):
    """Shape/dtype pytree of FLUX parameters WITHOUT materializing a single byte
    (``jax.eval_shape`` over init). The entry point for sharded-from-birth
    placement of models too big for one chip: feed the result to
    ``parallel.mesh.materialize_params_sharded`` (or a sharded checkpoint
    restore) so a flux-dev-class 12B pytree never exists unsharded anywhere."""
    module = FluxModel(cfg)
    x = jax.ShapeDtypeStruct(sample_shape, jnp.float32)
    t = jax.ShapeDtypeStruct((sample_shape[0],), jnp.float32)
    ctx = jax.ShapeDtypeStruct((sample_shape[0], txt_len, cfg.context_in_dim), jnp.float32)
    y = jax.ShapeDtypeStruct((sample_shape[0], cfg.vec_in_dim), jnp.float32)
    return jax.eval_shape(
        lambda r, x_, t_, c_, y_: module.init(r, x_, t_, c_, y=y_)["params"],
        jax.random.key(0), x, t, ctx, y,
    )


def build_flux(
    cfg: FluxConfig,
    rng=None,
    sample_shape=(1, 32, 32, 16),
    txt_len=128,
    name="flux",
    params=None,
) -> DiffusionModel:
    """Build a FLUX DiffusionModel. ``params`` skips initialization entirely (the
    checkpoint-load path — initializing billions of params just to overwrite them
    would double the load cost)."""
    module = FluxModel(cfg)
    if params is None:
        if rng is None:
            raise ValueError("need rng to initialize (or pass params=)")
        x = jnp.zeros(sample_shape, jnp.float32)
        t = jnp.zeros((sample_shape[0],), jnp.float32)
        ctx = jnp.zeros((sample_shape[0], txt_len, cfg.context_in_dim), jnp.float32)
        y = jnp.zeros((sample_shape[0], cfg.vec_in_dim), jnp.float32)
        params = module.init(rng, x, t, ctx, y=y)["params"]

    def apply(params, x, timesteps, context=None, **kw):
        return module.apply({"params": params}, x, timesteps, context, **kw)

    return DiffusionModel(
        apply=apply,
        params=params,
        name=name,
        config=cfg,
        block_lists={
            "double_blocks": cfg.depth,
            "single_blocks": cfg.depth_single_blocks,
        },
        pipeline_spec=_flux_pipeline_spec(module, cfg),
    )
