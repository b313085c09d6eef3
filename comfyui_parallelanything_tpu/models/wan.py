"""WAN-class video DiT — flax.linen, bf16, TPU-first. The video model family.

Capability target: the reference's README lists WAN2.2 among its tested workloads
(/root/reference/README.md:5) and its config scraper preserves video ctor kwargs —
``num_frames``, ``temporal_dim``, ``video_length`` (any_device_parallel.py:286-296).
Its pipeline mode walks a flat ``blocks``-style transformer list; this model exposes
exactly that (block list name ``blocks``, SURVEY §2b's ['...','layers'] walk).

Fresh TPU implementation of the public WAN recipe (not a port): 3D latent video
(B, T, H, W, C) patchified (1×2×2) into space-time tokens; sinusoidal timestep → MLP →
6-way adaLN modulation; N identical blocks of [modulated self-attention over all
space-time tokens with 3-axis (t, h, w) RoPE + q/k RMSNorm] → [cross-attention to text
context] → [modulated GELU FFN]; modulated head projecting back to patches. Attention
runs through the pluggable backend (ops/attention.py) — the space-time token count
(T·H·W/4) is exactly the long-sequence case sequence parallelism (parallel/sequence.py)
exists for.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import attention, count_qk_prologue
from ..ops.basic import modulate as _modulate, rms_normalize, timestep_embedding
from ..ops.rope import apply_rope, axis_rope_freqs
from .api import DiffusionModel, PipelineSegment, PipelineSpec


@dataclasses.dataclass(frozen=True)
class WanConfig:
    in_channels: int = 16
    out_channels: int = 16
    hidden_size: int = 1536
    ffn_dim: int = 8960
    num_heads: int = 12
    depth: int = 30
    text_dim: int = 4096       # umt5-xxl features
    freq_dim: int = 256        # sinusoidal timestep embedding width
    patch_size: tuple[int, int, int] = (1, 2, 2)  # (t, h, w)
    qk_norm_eps: float = 1e-6
    theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    # Rectified-flow velocity parameterization (see models/flux.py): routes the
    # KSampler node's k-sampler menu through flow-time sampling for WAN.
    prediction: str = "flow"
    # CLIP-vision context width (WAN2.1-style i2v checkpoints: the img_emb
    # MLP projects CLIP ViT-H penultimate states (B, 257, 1280) into extra
    # cross-attention context). None = no image branch (t2v, and WAN2.2 i2v
    # which dropped it in favor of pure channel-concat conditioning).
    img_dim: int | None = None

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def axes_dim(self) -> tuple[int, int, int]:
        """Per-axis RoPE dims over (t, h, w), summing to head_dim: the temporal axis
        takes the remainder after h/w get 2·(d//6) each (the public WAN split)."""
        d = self.head_dim
        hw = 2 * (d // 6)
        return (d - 2 * hw, hw, hw)


def wan_1_3b_config(**overrides) -> WanConfig:
    return dataclasses.replace(WanConfig(), **overrides)


def wan_14b_config(**overrides) -> WanConfig:
    base = WanConfig(hidden_size=5120, ffn_dim=13824, num_heads=40, depth=40)
    return dataclasses.replace(base, **overrides)


def wan_14b_i2v_config(**overrides) -> WanConfig:
    """The i2v variant: 36 in-channels = noisy latent 16 + frame mask 4 +
    encoded-image cond latent 16 (WAN2.2 channel-concat conditioning; no
    CLIP-vision branch)."""
    return wan_14b_config(in_channels=36, **overrides)


def wan_14b_i2v_clip_config(**overrides) -> WanConfig:
    """The WAN2.1-style i2v variant: channel-concat conditioning (36
    in-channels, as above) PLUS the CLIP-vision branch — ``img_emb.*``
    projects ViT-H penultimate states into 257 extra cross-attention context
    tokens served by per-block ``k_img``/``v_img`` heads. The reference's
    tested WAN set (/root/reference/README.md:5) includes these checkpoints."""
    return wan_14b_config(in_channels=36, img_dim=1280, **overrides)


class _RMSNorm(nn.Module):
    """RMSNorm in f32 with a learned scale over the last dim (WAN q/k norm runs
    over the full H·D inner dim before the head split)."""

    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return rms_normalize(x, scale, self.eps)


class _HeadModulation(nn.Module):
    """Learned (1, 2, D) bias + time vector → head shift/scale (the public WAN
    head). A submodule (not a bare ``self.param`` in setup) so its parameter is
    initialized lazily — pipeline stages that never run the head don't need it in
    their param subtree."""

    hidden: int

    @nn.compact
    def __call__(self, vec):
        mod = self.param(
            "bias", nn.initializers.normal(0.02), (1, 2, self.hidden)
        )
        return mod + vec[:, None, :]


class WanBlock(nn.Module):
    """Modulated self-attn (3-axis RoPE) → cross-attn(text) → modulated FFN."""

    cfg: WanConfig

    @nn.compact
    def __call__(self, x, context, e, rope, context_img=None):
        """x: (B, S, D) space-time tokens; context: (B, L, D) projected text;
        e: (B, 6, D) f32 modulation chunks; rope: (cos, sin); context_img:
        optional (B, Li, D) projected CLIP-vision tokens (WAN2.1-style i2v) —
        attended by dedicated k_img/v_img heads and summed with the text
        cross-attention before the output projection (the public i2v
        cross-attn: one extra attention over image context, same queries)."""
        cfg = self.cfg
        H, D = cfg.num_heads, cfg.head_dim
        # Learned per-block modulation bias added to the shared time modulation.
        mod_bias = self.param(
            "modulation", nn.initializers.normal(0.02), (1, 6, cfg.hidden_size)
        )
        e = (e + mod_bias).astype(jnp.float32)
        shift1, scale1, gate1, shift2, scale2, gate2 = (
            e[:, i][:, None, :] for i in range(6)
        )

        B, S, _ = x.shape

        # -- self-attention over all space-time tokens ----------------------------
        # q/k RMSNorm runs over the FULL inner dim (H·D) before the head split —
        # the public WAN convention (norm_q/norm_k are RMSNorm(dim)); per-head
        # norm would be numerically different and break checkpoint fidelity.
        h = _modulate(
            nn.LayerNorm(use_bias=False, use_scale=False, dtype=cfg.dtype, name="norm1")(x),
            shift1, scale1,
        )
        q = nn.Dense(H * D, dtype=cfg.dtype, name="self_q")(h)
        k = nn.Dense(H * D, dtype=cfg.dtype, name="self_k")(h)
        v = nn.Dense(H * D, dtype=cfg.dtype, name="self_v")(h)
        q = _RMSNorm(cfg.qk_norm_eps, name="self_q_norm")(q).reshape(B, S, H, D)
        k = _RMSNorm(cfg.qk_norm_eps, name="self_k_norm")(k).reshape(B, S, H, D)
        v = v.reshape(B, S, H, D)
        cos, sin = rope
        # The fused q/k prologue (ops/attention.qk_prologue) norms a HEAD;
        # this norm runs over the full width, so norm and rotary take the jnp
        # functions — counted where the other families' prologues are, once a
        # trace.
        count_qk_prologue(fused=False, rope=True)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        attn = attention(q, k, v).reshape(B, S, -1)
        attn = nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="self_o")(attn)
        x = x + gate1.astype(cfg.dtype) * attn

        # -- cross-attention to text (no rope, no gate; affine pre-norm) ----------
        L = context.shape[1]
        h = nn.LayerNorm(dtype=cfg.dtype, name="norm3")(x)
        q = nn.Dense(H * D, dtype=cfg.dtype, name="cross_q")(h)
        k = nn.Dense(H * D, dtype=cfg.dtype, name="cross_k")(context)
        v = nn.Dense(H * D, dtype=cfg.dtype, name="cross_v")(context)
        q = _RMSNorm(cfg.qk_norm_eps, name="cross_q_norm")(q).reshape(B, S, H, D)
        k = _RMSNorm(cfg.qk_norm_eps, name="cross_k_norm")(k).reshape(B, L, H, D)
        v = v.reshape(B, L, H, D)
        attn = attention(q, k, v)
        if context_img is not None:
            Li = context_img.shape[1]
            k_i = nn.Dense(H * D, dtype=cfg.dtype, name="cross_k_img")(context_img)
            v_i = nn.Dense(H * D, dtype=cfg.dtype, name="cross_v_img")(context_img)
            k_i = _RMSNorm(cfg.qk_norm_eps, name="cross_k_img_norm")(k_i)
            attn = attn + attention(
                q, k_i.reshape(B, Li, H, D), v_i.reshape(B, Li, H, D)
            )
        attn = attn.reshape(B, S, -1)
        x = x + nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="cross_o")(attn)

        # -- FFN -------------------------------------------------------------------
        h = _modulate(
            nn.LayerNorm(use_bias=False, use_scale=False, dtype=cfg.dtype, name="norm2")(x),
            shift2, scale2,
        )
        h = nn.Dense(cfg.ffn_dim, dtype=cfg.dtype, name="ffn_in")(h)
        h = nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="ffn_out")(nn.gelu(h))
        return x + gate2.astype(cfg.dtype) * h


class WanModel(nn.Module):
    """forward(x video latent (B, T, H, W, C), timesteps (B,), context (B, L, text_dim)).

    Setup-style for the staged pipeline decomposition (same protocol as FluxModel):
    carry = {x, context, e, vec, rope_cos, rope_sin}.
    """

    cfg: WanConfig

    def setup(self):
        cfg = self.cfg
        self.patch_embedding = nn.Dense(cfg.hidden_size, dtype=cfg.dtype)
        self.text_in = nn.Dense(cfg.hidden_size, dtype=cfg.dtype)
        self.text_hidden = nn.Dense(cfg.hidden_size, dtype=cfg.dtype)
        self.time_in = nn.Dense(cfg.hidden_size, dtype=jnp.float32)
        self.time_hidden = nn.Dense(cfg.hidden_size, dtype=jnp.float32)
        self.time_projection = nn.Dense(6 * cfg.hidden_size, dtype=jnp.float32)
        self.blocks = [WanBlock(cfg) for _ in range(cfg.depth)]
        if cfg.img_dim is not None:
            # The public i2v img_emb MLPProj: LN(img_dim) → Dense → GELU →
            # Dense → LN(hidden), projecting CLIP-vision penultimate states
            # into extra cross-attention context tokens.
            self.img_ln_in = nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32)
            self.img_in = nn.Dense(cfg.hidden_size, dtype=cfg.dtype)
            self.img_hidden = nn.Dense(cfg.hidden_size, dtype=cfg.dtype)
            self.img_ln_out = nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32)
        # Head modulation is a learned (1, 2, D) bias added to the time vector —
        # the public WAN head (head.modulation + e), NOT a projection.
        self.head_modulation = _HeadModulation(cfg.hidden_size)
        self.head_norm = nn.LayerNorm(use_bias=False, use_scale=False, dtype=cfg.dtype)
        pt, ph, pw = cfg.patch_size
        self.head_proj = nn.Dense(pt * ph * pw * cfg.out_channels, dtype=jnp.float32)

    def prepare(self, x, timesteps, context=None, clip_fea=None, **kwargs):
        cfg = self.cfg
        B, T, Hh, Ww, C = x.shape
        pt, ph, pw = cfg.patch_size
        tp, hp, wp = T // pt, Hh // ph, Ww // pw

        # (1, 2, 2) patchify → (B, tp·hp·wp, pt·ph·pw·C)
        tok = x.astype(cfg.dtype).reshape(B, tp, pt, hp, ph, wp, pw, C)
        tok = tok.transpose(0, 1, 3, 5, 2, 4, 6, 7).reshape(
            B, tp * hp * wp, pt * ph * pw * C
        )
        tok = self.patch_embedding(tok)

        if context is None:
            raise ValueError("WAN requires text context tokens")
        ctx = self.text_hidden(
            nn.gelu(self.text_in(context.astype(cfg.dtype)))
        )

        vec = self.time_hidden(
            nn.silu(
                self.time_in(
                    timestep_embedding(timesteps, cfg.freq_dim, time_factor=1000.0)
                )
            )
        )
        e = self.time_projection(nn.silu(vec)).reshape(B, 6, cfg.hidden_size)
        vec = vec.astype(jnp.float32)  # carried for the head modulation

        # 3-axis (t, h, w) position ids for RoPE.
        tt = jnp.arange(tp, dtype=jnp.int32)
        hh = jnp.arange(hp, dtype=jnp.int32)
        ww = jnp.arange(wp, dtype=jnp.int32)
        grid = jnp.stack(
            jnp.meshgrid(tt, hh, ww, indexing="ij"), axis=-1
        ).reshape(1, tp * hp * wp, 3)
        ids = jnp.broadcast_to(grid, (B, tp * hp * wp, 3))
        cos, sin = axis_rope_freqs(ids, self.cfg.axes_dim, cfg.theta)
        carry = {
            "x": tok, "context": ctx, "e": e, "vec": vec,
            "rope_cos": cos, "rope_sin": sin,
        }
        if clip_fea is not None:
            if cfg.img_dim is None:
                raise ValueError(
                    "clip_fea passed but this WAN config has no CLIP-vision "
                    "branch (img_dim=None) — load a WAN2.1-style i2v "
                    "checkpoint (wan_14b_i2v_clip_config)"
                )
            ci = self.img_ln_in(clip_fea.astype(jnp.float32))
            ci = self.img_hidden(nn.gelu(self.img_in(ci.astype(cfg.dtype))))
            carry["context_img"] = self.img_ln_out(ci).astype(cfg.dtype)
        return carry

    def block_step(self, carry, i: int):
        x = self.blocks[i](
            carry["x"], carry["context"], carry["e"],
            (carry["rope_cos"], carry["rope_sin"]),
            context_img=carry.get("context_img"),
        )
        return {**carry, "x": x}

    def finalize(self, carry, out_shape: tuple[int, ...]):
        cfg = self.cfg
        B, T, Hh, Ww, _ = out_shape
        pt, ph, pw = cfg.patch_size
        tp, hp, wp = T // pt, Hh // ph, Ww // pw
        x, vec = carry["x"], carry["vec"]
        mod = self.head_modulation(vec)
        shift, scale = mod[:, 0][:, None, :], mod[:, 1][:, None, :]
        x = _modulate(self.head_norm(x), shift, scale)
        x = self.head_proj(x.astype(jnp.float32))
        x = x.reshape(B, tp, hp, wp, pt, ph, pw, cfg.out_channels)
        x = x.transpose(0, 1, 4, 2, 5, 3, 6, 7)
        return x.reshape(B, T, Hh, Ww, cfg.out_channels)

    def __call__(self, x, timesteps, context=None, clip_fea=None, **kwargs):
        carry = self.prepare(x, timesteps, context, clip_fea=clip_fea)
        for i in range(self.cfg.depth):
            carry = self.block_step(carry, i)
        return self.finalize(carry, x.shape)


def _wan_pipeline_spec(module: WanModel, cfg: WanConfig) -> PipelineSpec:
    def prepare(params, x, t, context=None, clip_fea=None, **kw):
        return module.apply(
            {"params": params}, x, t, context, clip_fea=clip_fea,
            method=WanModel.prepare,
        )

    def make_block(i):
        def fn(params, carry):
            return module.apply({"params": params}, carry, i, method=WanModel.block_step)

        return fn

    def finalize(params, carry, out_shape):
        return module.apply(
            {"params": params}, carry, out_shape, method=WanModel.finalize
        )

    prepare_keys = (
        "patch_embedding", "text_in", "text_hidden",
        "time_in", "time_hidden", "time_projection",
    )
    if cfg.img_dim is not None:
        prepare_keys += ("img_ln_in", "img_in", "img_hidden", "img_ln_out")
    return PipelineSpec(
        prepare_keys=prepare_keys,
        prepare=prepare,
        segments=tuple(
            PipelineSegment((f"blocks_{i}",), make_block(i), f"blocks[{i}]")
            for i in range(cfg.depth)
        ),
        finalize_keys=("head_modulation", "head_proj"),
        finalize=finalize,
    )


def apply_i2v_conditioning(base: DiffusionModel, cond=None, clip_fea=None):
    """Compose WAN i2v conditioning into a DiffusionModel: every denoise
    step's input becomes ``concat([x, cond], channel)`` (``cond`` = 4-channel
    frame mask ‖ encoded start-frames latent, the WAN i2v channel-concat
    contract) and, when ``clip_fea`` is given (WAN2.1-style checkpoints with
    the img_emb branch), the CLIP-vision penultimate states ride the call as
    the ``clip_fea`` kwarg. Like ``apply_inpaint_conditioning``
    (models/unet.py), the conditioning tensors live in the merged params
    pytree so the composition places/shards through ``parallelize`` and the
    whole step stays one jit program. CFG's doubled batch (cond ‖ uncond in
    one forward) tiles both tensors. The reference's WAN i2v workloads get
    this conditioning from the host model it wraps
    (any_device_parallel.py:921-930 unwraps it; /root/reference/README.md:5
    lists WAN2.2 in the tested set).

    Config-aware (host WAN21.concat_cond semantics): on a t2v model
    (in_channels == out_channels) the channel-concat tag is IGNORED with a
    warning (stock models without extra channels never call concat_cond); on
    an i2v model with no start-image cond, the missing channels zero-fill
    (stock zero-fills concat_latent_image, so a WanImageToVideo wired with
    only clip_vision_output still samples); a cond of the wrong width raises
    at compose time instead of dying in patchify."""
    cfg = base.config
    expected = None
    in_ch = getattr(cfg, "in_channels", None)
    out_ch = getattr(cfg, "out_channels", None)
    if in_ch is not None and out_ch is not None:
        expected = in_ch - out_ch  # extra channels the checkpoint consumes
        if expected <= 0:
            if cond is not None or clip_fea is not None:
                from ..utils.logging import get_logger

                get_logger().warning(
                    "i2v conditioning on a t2v checkpoint (in_channels == "
                    f"{in_ch}, no concat slots) — ignored, sampling proceeds "
                    "unconditioned (stock concat_cond semantics)"
                )
            return base
        if cond is not None and cond.shape[-1] != expected:
            raise ValueError(
                f"i2v cond carries {cond.shape[-1]} channels but the "
                f"checkpoint concatenates {expected} "
                f"(in {in_ch} − latent {out_ch}) — the WanImageToVideo VAE "
                "does not match this model"
            )
    if clip_fea is not None and getattr(cfg, "img_dim", None) is None:
        # A WAN2.1-template graph (clip_vision_output wired) reused on a
        # checkpoint without the img_emb branch (WAN2.2 i2v, t2v): stock's
        # model simply ignores clip_fea when it has no img_emb — degrade the
        # same way instead of raising mid-sampling in WanModel.prepare.
        from ..utils.logging import get_logger

        get_logger().warning(
            "clip_vision_output on a WAN checkpoint without the CLIP-vision "
            "branch (no img_emb weights; WAN2.2-style) — image embeds "
            "ignored, channel-concat conditioning still applies"
        )
        clip_fea = None
    merged: dict = {"base": base.params}
    if cond is not None:
        merged["cond"] = jnp.asarray(cond)
    if clip_fea is not None:
        merged["clip_fea"] = jnp.asarray(clip_fea)
    base_apply = base.apply
    fill_ch = expected if cond is None else None

    def _tile_to(a, batch, ndim):
        if a.shape[0] != batch:
            if batch % a.shape[0]:
                raise ValueError(
                    f"i2v conditioning batch {a.shape[0]} does not divide "
                    f"model batch {batch}"
                )
            a = jnp.tile(
                a, (batch // a.shape[0],) + (1,) * (ndim - 1)
            )
        return a

    def apply(p, x, timesteps, context=None, **kw):
        x_in = x
        if "cond" in p:
            c = _tile_to(p["cond"], x.shape[0], x.ndim)
            x_in = jnp.concatenate([x, c.astype(x.dtype)], axis=-1)
        elif fill_ch:
            # No start-image cond on an i2v checkpoint: zero-fill the concat
            # slots (zeros frame mask = nothing given, zeros cond latent).
            x_in = jnp.concatenate(
                [x, jnp.zeros(x.shape[:-1] + (fill_ch,), x.dtype)], axis=-1
            )
        if "clip_fea" in p:
            kw = {**kw, "clip_fea": _tile_to(p["clip_fea"], x.shape[0], 3)}
        return base_apply(p["base"], x_in, timesteps, context, **kw)

    return DiffusionModel(
        apply=apply, params=merged, name=f"{base.name}+i2v",
        config=base.config,
    )


def build_wan(
    cfg: WanConfig,
    rng=None,
    sample_shape=(1, 4, 16, 16, 16),
    txt_len=64,
    name="wan",
    params=None,
) -> DiffusionModel:
    """Build a WAN DiffusionModel; ``params`` skips initialization (load path).
    Two models of one configuration — WAN2.2's two experts — share ONE module
    and ``apply`` function, and so one traced and compiled step program
    between them: jax's caches are keyed on the function, whichever jitted
    wrapper (``DiffusionModel.__call__`` names one a model) calls it."""
    module, apply, spec = _wan_program(cfg)
    if params is None:
        if rng is None:
            raise ValueError("need rng to initialize (or pass params=)")
        x = jnp.zeros(sample_shape, jnp.float32)
        t = jnp.zeros((sample_shape[0],), jnp.float32)
        ctx = jnp.zeros((sample_shape[0], txt_len, cfg.text_dim), jnp.float32)
        kwargs = {}
        if cfg.img_dim is not None:
            # 257 = CLIP ViT penultimate tokens (CLS + 16² patches); init must
            # trace the image branch so its params exist in the pytree.
            kwargs["clip_fea"] = jnp.zeros(
                (sample_shape[0], 257, cfg.img_dim), jnp.float32
            )
        params = module.init(rng, x, t, ctx, **kwargs)["params"]
    return DiffusionModel(
        apply=apply,
        params=params,
        name=name,
        config=cfg,
        block_lists={"blocks": cfg.depth},
        pipeline_spec=spec,
    )


@functools.lru_cache(maxsize=None)
def _wan_program(cfg: WanConfig):
    """(module, apply, pipeline spec) of one configuration, made once."""
    module = WanModel(cfg)

    def apply(params, x, timesteps, context=None, **kw):
        return module.apply({"params": params}, x, timesteps, context, **kw)

    return module, apply, _wan_pipeline_spec(module, cfg)
