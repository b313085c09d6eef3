"""SD3/SD3.5-class MMDiT — flax.linen, bf16, TPU-first.

The reference wraps whatever diffusion model its host hands it (duck-typed
unwrap, any_device_parallel.py:921-930) — SD3-family checkpoints included.
Standalone, this is that family: dual-stream joint-attention blocks the whole
depth (no fused single blocks — the FLUX distinction), learned-at-checkpoint
sincos position table cropped to the sample grid (no RoPE), pooled CLIP(L+G)
vector + timestep modulation, optional per-head q/k RMS norm (the 3.5 models).

Same staged decomposition as models/flux.py (prepare / block_step / finalize)
so the batch==1 pipeline placement mode works identically. All three public
variants convert and run: sd3-medium, sd3.5-large, and sd3.5-medium — the
mmdit-x dual-attention x-blocks the medium model adds are implemented via
``x_block_self_attn_layers`` below (the converter infers the indices from the
checkpoint's ``joint_blocks.{i}.x_block.attn2`` keys; loader preset
``sd35-medium``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import attention, qk_prologue
from ..ops.basic import modulate as _modulate, timestep_embedding
from .api import DiffusionModel, PipelineSegment, PipelineSpec
from .flux import FusedQKV


@dataclasses.dataclass(frozen=True)
class MMDiTConfig:
    in_channels: int = 16          # latent channels (token width = p²·C)
    patch_size: int = 2
    depth: int = 24                # joint blocks; hidden = 64·depth, heads = depth
    context_in_dim: int = 4096     # T5 ‖ padded CLIP joint stream
    pooled_dim: int = 2048         # CLIP-L ‖ CLIP-G pooled
    pos_embed_max: int = 192       # checkpoint pos table is (max², hidden), cropped
    mlp_ratio: float = 4.0
    qk_norm: bool = False          # SD3.5 adds per-head q/k RMS norm
    # SD3.5-medium (mmdit-x): block indices with a SECOND self-attention over the
    # x stream only (dual attention). The converter infers this from which
    # joint_blocks.{i}.x_block.attn2 keys exist in the checkpoint.
    x_block_self_attn_layers: tuple[int, ...] = ()
    dtype: Any = jnp.bfloat16
    # SD3-family MMDiTs are rectified-flow models (see models/flux.py): the
    # KSampler node reads this to route them through flow-time k-sampling.
    prediction: str = "flow"

    @property
    def hidden_size(self) -> int:
        return 64 * self.depth

    @property
    def num_heads(self) -> int:
        return self.depth

    @property
    def head_dim(self) -> int:
        return 64


def sd3_medium_config(**overrides) -> MMDiTConfig:
    """SD3-medium (2B): depth 24, no q/k norm."""
    return dataclasses.replace(MMDiTConfig(), **overrides)


def sd35_large_config(**overrides) -> MMDiTConfig:
    """SD3.5-large (8B): depth 38, q/k RMS norm."""
    base = MMDiTConfig(depth=38, qk_norm=True)
    return dataclasses.replace(base, **overrides)


def sd35_medium_config(**overrides) -> MMDiTConfig:
    """SD3.5-medium (2.5B, mmdit-x): depth 24, q/k RMS norm, dual attention in
    the first 13 blocks (the published checkpoint's x_block_self_attn_layers —
    convert_mmdit_checkpoint re-infers the exact set from the state dict)."""
    base = MMDiTConfig(
        depth=24,
        qk_norm=True,
        pos_embed_max=384,
        x_block_self_attn_layers=tuple(range(13)),
    )
    return dataclasses.replace(base, **overrides)


def sincos_pos_embed(max_size: int, dim: int) -> np.ndarray:
    """The fixed 2-D sincos table SD3 ships in its checkpoints (stored there;
    regenerated here for from-scratch init): (max_size², dim), half the width
    per axis."""
    def axis_table(n, d):
        omega = 1.0 / (10000 ** (np.arange(d // 2, dtype=np.float64) / (d // 2)))
        out = np.einsum("p,f->pf", np.arange(n, dtype=np.float64), omega)
        return np.concatenate([np.sin(out), np.cos(out)], axis=1)

    grid_h = axis_table(max_size, dim // 2)
    grid_w = axis_table(max_size, dim // 2)
    # SAI's get_2d_sincos_pos_embed concatenates the WIDTH-axis embedding
    # first (meshgrid(grid_w, grid_h), grid[0] = w); match it so regenerated
    # tables line up with checkpoint-shipped ones.
    table = np.concatenate(
        [
            np.tile(grid_w, (max_size, 1)),
            np.repeat(grid_h, max_size, axis=0),
        ],
        axis=1,
    )
    return table.astype(np.float32)


class _VecEmbedder(nn.Module):
    """timestep/pooled MLP (SiLU between two Dense) — SAI's TimestepEmbedder/
    VectorEmbedder shape."""

    cfg: MMDiTConfig

    @nn.compact
    def __call__(self, x):
        h = nn.Dense(self.cfg.hidden_size, dtype=self.cfg.dtype, name="in_layer")(x)
        return nn.Dense(
            self.cfg.hidden_size, dtype=self.cfg.dtype, name="out_layer"
        )(nn.silu(h))


class _AdaLN(nn.Module):
    """vec → n_chunks modulation tensors (f32), SAI chunk order."""

    cfg: MMDiTConfig
    n_chunks: int

    @nn.compact
    def __call__(self, vec):
        out = nn.Dense(
            self.n_chunks * self.cfg.hidden_size, dtype=jnp.float32, name="lin"
        )(nn.silu(vec.astype(jnp.float32)))
        return jnp.split(out[:, None, :], self.n_chunks, axis=-1)


class _StreamAttnIn(nn.Module):
    """Pre-norm + modulation + fused qkv (+ optional per-head q/k RMS)."""

    cfg: MMDiTConfig

    @nn.compact
    def __call__(self, x, shift, scale):
        cfg = self.cfg
        H, D = cfg.num_heads, cfg.head_dim
        h = nn.LayerNorm(
            use_bias=False, use_scale=False, epsilon=1e-6, dtype=cfg.dtype,
            name="norm",
        )(x)
        h = _modulate(h, shift, scale)
        flat = FusedQKV(H, D, cfg.dtype, name="qkv")(h)  # (B, S, 3·H·D)
        qkv = flat.reshape(*x.shape[:2], 3, H, D)
        q, k = qkv[:, :, 0], qkv[:, :, 1]
        v = flat[..., 2 * H * D:].reshape(*x.shape[:2], H, D)
        if cfg.qk_norm:
            # q and k are read from the projection's output where they lie.
            q, k = qk_prologue(
                qkv, self.param("ln_q", nn.initializers.ones, (D,)),
                self.param("ln_k", nn.initializers.ones, (D,)))
        return h, q, k, v


class JointBlock(nn.Module):
    """One MMDiT block: context + x streams modulate/qkv separately, attend
    jointly over [context ‖ x], then per-stream proj/MLP. ``pre_only`` (the
    final block's context side) contributes qkv to the joint attention but has
    no output path — the context stream ends there."""

    cfg: MMDiTConfig
    pre_only: bool = False
    dual_attn: bool = False

    @nn.compact
    def __call__(self, x, ctx, vec):
        cfg = self.cfg
        mlp_dim = int(cfg.hidden_size * cfg.mlp_ratio)

        if self.dual_attn:
            # mmdit-x (SD3.5-medium): 9-chunk x-side adaLN — the extra triple
            # modulates a SECOND self-attention over the x stream alone, fed from
            # the same pre-norm output (SAI chunk order: attn, mlp, attn2).
            (xs1, xc1, xg1, xs2, xc2, xg2, x2s, x2c, x2g) = _AdaLN(
                cfg, 9, name="x_adaln"
            )(vec)
            _, q2, k2, v2 = _StreamAttnIn(cfg, name="x_attn_in2")(x, x2s, x2c)
        else:
            x_mods = _AdaLN(cfg, 6, name="x_adaln")(vec)
            (xs1, xc1, xg1, xs2, xc2, xg2) = x_mods
        _, xq, xk, xv = _StreamAttnIn(cfg, name="x_attn_in")(x, xs1, xc1)

        if self.pre_only:
            cs1, cc1 = _AdaLN(cfg, 2, name="ctx_adaln")(vec)
            _, cq, ck, cv = _StreamAttnIn(cfg, name="ctx_attn_in")(ctx, cs1, cc1)
        else:
            (cs1, cc1, cg1, cs2, cc2, cg2) = _AdaLN(cfg, 6, name="ctx_adaln")(vec)
            _, cq, ck, cv = _StreamAttnIn(cfg, name="ctx_attn_in")(ctx, cs1, cc1)

        ctx_len = ctx.shape[1]
        q = jnp.concatenate([cq, xq], axis=1)
        k = jnp.concatenate([ck, xk], axis=1)
        v = jnp.concatenate([cv, xv], axis=1)
        attn_out = attention(q, k, v)
        attn_out = attn_out.reshape(attn_out.shape[0], attn_out.shape[1], -1)
        ctx_attn, x_attn = attn_out[:, :ctx_len], attn_out[:, ctx_len:]

        x = x + xg1.astype(cfg.dtype) * nn.Dense(
            cfg.hidden_size, dtype=cfg.dtype, name="x_attn_proj"
        )(x_attn)
        if self.dual_attn:
            attn2 = attention(q2, k2, v2)
            attn2 = attn2.reshape(attn2.shape[0], attn2.shape[1], -1)
            x = x + x2g.astype(cfg.dtype) * nn.Dense(
                cfg.hidden_size, dtype=cfg.dtype, name="x_attn2_proj"
            )(attn2)
        xm = nn.LayerNorm(
            use_bias=False, use_scale=False, epsilon=1e-6, dtype=cfg.dtype,
            name="x_norm2",
        )(x)
        x = x + xg2.astype(cfg.dtype) * nn.Sequential([
            nn.Dense(mlp_dim, dtype=cfg.dtype, name="x_mlp_in"),
            lambda t: nn.gelu(t, approximate=True),
            nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="x_mlp_out"),
        ])(_modulate(xm, xs2, xc2))

        if self.pre_only:
            return x, ctx
        ctx = ctx + cg1.astype(cfg.dtype) * nn.Dense(
            cfg.hidden_size, dtype=cfg.dtype, name="ctx_attn_proj"
        )(ctx_attn)
        cm = nn.LayerNorm(
            use_bias=False, use_scale=False, epsilon=1e-6, dtype=cfg.dtype,
            name="ctx_norm2",
        )(ctx)
        ctx = ctx + cg2.astype(cfg.dtype) * nn.Sequential([
            nn.Dense(mlp_dim, dtype=cfg.dtype, name="ctx_mlp_in"),
            lambda t: nn.gelu(t, approximate=True),
            nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="ctx_mlp_out"),
        ])(_modulate(cm, cs2, cc2))
        return x, ctx


class _PosTable(nn.Module):
    """The checkpoint's (max², hidden) sincos table as a lazily-materialized
    submodule (a bare self.param in setup would be demanded by every staged
    sub-pytree apply; submodule params materialize only when called)."""

    cfg: MMDiTConfig

    @nn.compact
    def __call__(self):
        return self.param(
            "table",
            lambda key: jnp.asarray(
                sincos_pos_embed(self.cfg.pos_embed_max, self.cfg.hidden_size)
            ),
        )


class MMDiTModel(nn.Module):
    """forward(x latent NHWC, timesteps (B,) flow-time in [0,1], context
    (B,S,4096), y=(B,2048) pooled). Staged like FluxModel for pipeline mode."""

    cfg: MMDiTConfig

    def setup(self):
        cfg = self.cfg
        token_dim = cfg.patch_size * cfg.patch_size * cfg.in_channels
        self.x_in = nn.Dense(cfg.hidden_size, dtype=cfg.dtype)
        self.pos_embed = _PosTable(cfg)
        self.context_in = nn.Dense(cfg.hidden_size, dtype=cfg.dtype)
        self.time_in = _VecEmbedder(cfg)
        self.vector_in = _VecEmbedder(cfg)
        self.blocks = [
            JointBlock(
                cfg,
                pre_only=(i == cfg.depth - 1),
                dual_attn=(i in cfg.x_block_self_attn_layers),
            )
            for i in range(cfg.depth)
        ]
        self.final_mod = nn.Dense(2 * cfg.hidden_size, dtype=jnp.float32)
        self.final_norm = nn.LayerNorm(
            use_bias=False, use_scale=False, epsilon=1e-6, dtype=cfg.dtype
        )
        self.final_proj = nn.Dense(token_dim, dtype=jnp.float32)

    def _cropped_pos(self, hp: int, wp: int):
        """Center-crop the (max², hidden) table to the (hp, wp) token grid —
        SD3's cropped_pos_embed."""
        m = self.cfg.pos_embed_max
        if hp > m or wp > m:
            raise ValueError(f"latent grid {hp}x{wp} exceeds pos table {m}x{m}")
        top = (m - hp) // 2
        left = (m - wp) // 2
        table = self.pos_embed().reshape(m, m, -1)
        return table[top : top + hp, left : left + wp].reshape(1, hp * wp, -1)

    def prepare(self, x, timesteps, context=None, y=None, **kwargs):
        cfg = self.cfg
        B, Hh, Ww, C = x.shape
        p = cfg.patch_size
        hp, wp = Hh // p, Ww // p

        img = x.astype(cfg.dtype).reshape(B, hp, p, wp, p, C)
        img = img.transpose(0, 1, 3, 2, 4, 5).reshape(B, hp * wp, p * p * C)
        img = self.x_in(img) + self._cropped_pos(hp, wp).astype(cfg.dtype)

        if context is None:
            raise ValueError("SD3 requires text context tokens")
        ctx = self.context_in(context.astype(cfg.dtype))

        vec = self.time_in(
            timestep_embedding(timesteps, 256, time_factor=1000.0).astype(cfg.dtype)
        )
        if y is None:
            y = jnp.zeros((B, cfg.pooled_dim), jnp.float32)
        vec = vec + self.vector_in(y.astype(cfg.dtype))
        return {"img": img, "ctx": ctx, "vec": vec}

    def block_step(self, carry, i: int):
        img, ctx = self.blocks[i](carry["img"], carry["ctx"], carry["vec"])
        return {**carry, "img": img, "ctx": ctx}

    def finalize(self, carry, out_shape: tuple[int, ...]):
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

    def __call__(self, x, timesteps, context=None, y=None, **kwargs):
        carry = self.prepare(x, timesteps, context, y=y)
        for i in range(self.cfg.depth):
            carry = self.block_step(carry, i)
        return self.finalize(carry, x.shape)


def _mmdit_pipeline_spec(module: MMDiTModel, cfg: MMDiTConfig) -> PipelineSpec:
    def prepare(params, x, t, context=None, **kw):
        return module.apply({"params": params}, x, t, context, **kw,
                            method=MMDiTModel.prepare)

    def make_block(i):
        def fn(params, carry):
            return module.apply({"params": params}, carry, i,
                                method=MMDiTModel.block_step)
        return fn

    def finalize(params, carry, out_shape):
        return module.apply({"params": params}, carry, out_shape,
                            method=MMDiTModel.finalize)

    prepare_keys = ("x_in", "pos_embed", "context_in", "time_in", "vector_in")
    return PipelineSpec(
        prepare_keys=prepare_keys,
        prepare=prepare,
        segments=tuple(
            PipelineSegment((f"blocks_{i}",), make_block(i), label=f"joint_{i}")
            for i in range(cfg.depth)
        ),
        finalize_keys=("final_mod", "final_proj"),  # final_norm is affine-free (no params)
        finalize=finalize,
    )


def build_mmdit(
    cfg: MMDiTConfig,
    rng=None,
    params=None,
    sample_shape=(1, 32, 32, 16),
    txt_len: int = 77,
    name: str = "mmdit",
) -> DiffusionModel:
    """Initialize (or wrap converted ``params``) an SD3-class MMDiT."""
    module = MMDiTModel(cfg)
    if params is None:
        if rng is None:
            raise ValueError("need rng to initialize (or pass params=)")
        x = jnp.zeros(sample_shape, jnp.float32)
        t = jnp.zeros((sample_shape[0],), jnp.float32)
        c = jnp.zeros((sample_shape[0], txt_len, cfg.context_in_dim), jnp.float32)
        params = module.init(rng, x, t, c)["params"]

    def apply(params, x, timesteps, context=None, **kw):
        return module.apply({"params": params}, x, timesteps, context, **kw)

    return DiffusionModel(
        apply=apply,
        params=params,
        name=name,
        config=cfg,
        block_lists={"joint_blocks": cfg.depth},
        pipeline_spec=_mmdit_pipeline_spec(module, cfg),
        # The family's trained timestep shift (the host's SD3 sampling
        # default): a graph without a ModelSamplingSD3 node samples with it.
        sampler_prefs={"shift": 3.0},
    )
