"""SD-family latent UNet (SD1.5 / SDXL) — flax.linen, NHWC, TPU-first.

Capability target: the reference's benchmark ladder runs SD-class UNets replicated
per device (BASELINE configs 1-2; the reference extracts UNet ctor kwargs like
``num_res_blocks``/``channel_mult``/``adm_in_channels``/``transformer_depth`` when
cloning, any_device_parallel.py:286-296 — those are exactly the knobs of this config).
This is a fresh TPU implementation, not a port: NHWC layout (TPU conv-friendly),
bf16 compute / f32 params by policy, attention through the pluggable backend
(ops/attention.py), everything shape-static under jit.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax.numpy as jnp

from ..ops.attention import attention
from ..ops.basic import UpsampleConv, timestep_embedding
from .api import DiffusionModel


@dataclasses.dataclass(frozen=True)
class UNetConfig:
    in_channels: int = 4
    out_channels: int = 4
    model_channels: int = 320
    num_res_blocks: int = 2
    channel_mult: tuple[int, ...] = (1, 2, 4, 4)
    attention_levels: tuple[int, ...] = (0, 1, 2)
    transformer_depth: tuple[int, ...] = (1, 1, 1, 1)
    num_heads: int = 8
    context_dim: int = 768
    adm_in_channels: int | None = None  # SDXL pooled-text+size vector conditioning
    # Middle-block transformer depth override. None = derive from the deepest
    # encoder level (the SD1.5/SD2/SDXL-base pattern). The SDXL REFINER needs
    # it: no attention at its deepest encoder level but a depth-4 middle
    # transformer — underivable from the per-level tuple.
    transformer_depth_middle: int | None = None
    norm_groups: int = 32
    # Sampling parameterization the checkpoint was trained with ("eps" or "v");
    # carried on the config so samplers/nodes pick it up without a side channel
    # (ComfyUI keeps this in model_sampling the same way).
    prediction: str = "eps"
    # FreeU patch (Si et al. 2023; the host's FreeU/FreeU_V2 model patches):
    # (b1, b2, s1, s2, version) applied in the up path — backbone channels
    # scaled by b, skip connections low-pass-rescaled by s at the two
    # deepest-channel stages. None = off. Carried on the config (not a
    # runtime flag) so the patch composes with conversion/parallelize like
    # any other architecture knob: the patch node rebuilds the module around
    # the SAME params.
    freeu: tuple | None = None
    dtype: Any = jnp.bfloat16  # compute dtype; params stay f32


def sd15_config(**overrides) -> UNetConfig:
    """SD1.x UNet (v1-inference.yaml): attention at the three shallow levels
    and — like every ldm UNet — a depth-1 transformer in the middle block,
    which the per-level tuple cannot express (the deepest level has none)."""
    return dataclasses.replace(
        UNetConfig(transformer_depth_middle=1), **overrides
    )


def sd21_config(**overrides) -> UNetConfig:
    """SD2.x UNet: OpenCLIP-H context (1024) and fixed 64-dim heads. The 512
    base checkpoints are eps; the 768-v ones v-prediction — pass
    ``prediction="v"`` (or use the node family "sd21-v")."""
    base = UNetConfig(
        context_dim=1024, num_heads=-1, transformer_depth_middle=1
    )
    return dataclasses.replace(base, **overrides)


def sdxl_config(**overrides) -> UNetConfig:
    base = UNetConfig(
        model_channels=320,
        channel_mult=(1, 2, 4),
        attention_levels=(1, 2),
        transformer_depth=(0, 2, 10),
        num_heads=-1,  # SDXL uses fixed 64-dim heads; -1 → heads = channels // 64
        context_dim=2048,
        adm_in_channels=2816,
    )
    return dataclasses.replace(base, **overrides)


def sdxl_refiner_config(**overrides) -> UNetConfig:
    """SDXL-refiner UNet (sd_xl_refiner.yaml): 384 base channels, attention
    only at the middle two levels (depth 4) PLUS a depth-4 middle transformer,
    OpenCLIP-G-only context (1280), aesthetic-score adm (2560)."""
    base = UNetConfig(
        model_channels=384,
        channel_mult=(1, 2, 4, 4),
        attention_levels=(1, 2),
        transformer_depth=(0, 4, 4, 0),
        transformer_depth_middle=4,
        num_heads=-1,
        context_dim=1280,
        adm_in_channels=2560,
    )
    return dataclasses.replace(base, **overrides)


def _fourier_filter(x, threshold: int, scale: float):
    """FreeU's skip-connection low-frequency rescale: scale the centered
    ``2·threshold``-wide low-frequency box of the 2-D spectrum by ``scale``.
    FFT in f32 (TPU FFT is f32); cast back to the input dtype."""
    dtype = x.dtype
    xf = jnp.fft.fftshift(
        jnp.fft.fft2(x.astype(jnp.float32), axes=(1, 2)), axes=(1, 2)
    )
    B, H, W, C = x.shape
    cy, cx = H // 2, W // 2
    mask = jnp.ones((1, H, W, 1), jnp.float32)
    mask = mask.at[
        :, max(cy - threshold, 0):cy + threshold,
        max(cx - threshold, 0):cx + threshold, :,
    ].set(float(scale))
    out = jnp.fft.ifft2(
        jnp.fft.ifftshift(xf * mask, axes=(1, 2)), axes=(1, 2)
    ).real
    return out.astype(dtype)


def _apply_freeu(cfg: UNetConfig, h, skip):
    """FreeU on one up-block junction: when the backbone stream ``h`` sits at
    one of the two deepest channel widths, scale its first half-channels
    (constant ``b`` for v1; hidden-mean-modulated for v2 — the FreeU_V2
    improvement) and low-pass-rescale the skip by ``s``."""
    b1, b2, s1, s2, version = cfg.freeu
    C = h.shape[-1]
    # Stock keys the two stages on literal 4x and 2x the base width (1280/640
    # for both SD1.5 and SDXL) — NOT the channel_mult tail, which would
    # collide for SD1.5's (1, 2, 4, 4).
    stage = {cfg.model_channels * 4: (b1, s1),
             cfg.model_channels * 2: (b2, s2)}
    if C not in stage:
        return h, skip
    b, s = stage[C]
    half = C // 2
    if version >= 2:
        hidden_mean = jnp.mean(h.astype(jnp.float32), axis=-1, keepdims=True)
        dims = (1, 2, 3)
        h_min = jnp.min(hidden_mean, axis=dims, keepdims=True)
        h_max = jnp.max(hidden_mean, axis=dims, keepdims=True)
        hidden_mean = (hidden_mean - h_min) / jnp.maximum(h_max - h_min, 1e-8)
        scale = ((b - 1.0) * hidden_mean + 1.0).astype(h.dtype)
    else:
        scale = jnp.asarray(b, h.dtype)
    h = jnp.concatenate([h[..., :half] * scale, h[..., half:]], axis=-1)
    return h, _fourier_filter(skip, threshold=1, scale=s)


def middle_depth(cfg: UNetConfig) -> int:
    """Middle-block transformer depth — the ONE derivation shared by UNet2D,
    the checkpoint converter, and the ControlNet trunk (they must agree or
    conversion misindexes middle_block.{1,2})."""
    if cfg.transformer_depth_middle is not None:
        return cfg.transformer_depth_middle
    if len(cfg.channel_mult) - 1 in cfg.attention_levels:
        return cfg.transformer_depth[-1]
    return 0


def _heads_for(cfg: UNetConfig, channels: int) -> int:
    if cfg.num_heads == -1:
        return max(1, channels // 64)
    return cfg.num_heads


class ResBlock(nn.Module):
    cfg: UNetConfig
    out_ch: int

    @nn.compact
    def __call__(self, x, emb):
        cfg = self.cfg
        h = nn.GroupNorm(num_groups=cfg.norm_groups, dtype=cfg.dtype)(x)
        h = nn.silu(h)
        h = nn.Conv(self.out_ch, (3, 3), padding=1, dtype=cfg.dtype)(h)
        emb_out = nn.Dense(self.out_ch, dtype=cfg.dtype)(nn.silu(emb))
        h = h + emb_out[:, None, None, :]
        h = nn.GroupNorm(num_groups=cfg.norm_groups, dtype=cfg.dtype)(h)
        h = nn.silu(h)
        h = nn.Conv(self.out_ch, (3, 3), padding=1, dtype=cfg.dtype)(h)
        if x.shape[-1] != self.out_ch:
            x = nn.Conv(self.out_ch, (1, 1), dtype=cfg.dtype)(x)
        return x + h


class TransformerBlock(nn.Module):
    """LN → self-attn → LN → cross-attn(context) → LN → GEGLU MLP, pre-norm residual."""

    cfg: UNetConfig
    channels: int

    @nn.compact
    def __call__(self, x, context):
        cfg = self.cfg
        heads = _heads_for(cfg, self.channels)
        head_dim = self.channels // heads

        def mha(q_in, kv_in, name):
            q = nn.DenseGeneral((heads, head_dim), use_bias=False, dtype=cfg.dtype, name=f"{name}_q")(q_in)
            k = nn.DenseGeneral((heads, head_dim), use_bias=False, dtype=cfg.dtype, name=f"{name}_k")(kv_in)
            v = nn.DenseGeneral((heads, head_dim), use_bias=False, dtype=cfg.dtype, name=f"{name}_v")(kv_in)
            o = attention(q, k, v)
            return nn.DenseGeneral(self.channels, axis=(-2, -1), dtype=cfg.dtype, name=f"{name}_o")(o)

        h = nn.LayerNorm(dtype=cfg.dtype)(x)
        x = x + mha(h, h, "attn1")
        h = nn.LayerNorm(dtype=cfg.dtype)(x)
        ctx = h if context is None else context
        x = x + mha(h, ctx, "attn2")
        h = nn.LayerNorm(dtype=cfg.dtype)(x)
        gate = nn.Dense(self.channels * 8, dtype=cfg.dtype, name="ff_in")(h)
        a, b = jnp.split(gate, 2, axis=-1)
        # GEGLU with EXACT (erf) gelu — the ldm/diffusers convention for SD UNets
        # (FLUX-family models use tanh-approx; the two differ at ~1e-3, enough to
        # drift a 50-step sample).
        x = x + nn.Dense(self.channels, dtype=cfg.dtype, name="ff_out")(
            a * nn.gelu(b, approximate=False)
        )
        return x


class SpatialTransformer(nn.Module):
    cfg: UNetConfig
    channels: int
    depth: int

    @nn.compact
    def __call__(self, x, context):
        cfg = self.cfg
        B, H, W, C = x.shape
        h = nn.GroupNorm(num_groups=cfg.norm_groups, dtype=cfg.dtype)(x)
        h = nn.Conv(self.channels, (1, 1), dtype=cfg.dtype, name="proj_in")(h)
        h = h.reshape(B, H * W, self.channels)
        for i in range(self.depth):
            h = TransformerBlock(cfg, self.channels, name=f"block_{i}")(h, context)
        h = h.reshape(B, H, W, self.channels)
        h = nn.Conv(self.channels, (1, 1), dtype=cfg.dtype, name="proj_out")(h)
        return x + h


class Downsample(nn.Module):
    cfg: UNetConfig
    channels: int

    @nn.compact
    def __call__(self, x):
        return nn.Conv(self.channels, (3, 3), strides=(2, 2), padding=1, dtype=self.cfg.dtype)(x)


class Upsample(nn.Module):
    cfg: UNetConfig
    channels: int

    @nn.compact
    def __call__(self, x):
        # Named as the nn.Conv it stands for: the checkpoint maps' Conv_0.
        return UpsampleConv(self.channels, dtype=self.cfg.dtype, name="Conv_0")(x)


def _has_attn(cfg: UNetConfig, level: int) -> bool:
    return level in cfg.attention_levels and cfg.transformer_depth[level] > 0


def _input_schedule(cfg: UNetConfig) -> list[tuple[int, int]]:
    """(level, i) of every input (down) block, in execution order."""
    return [
        (level, i)
        for level in range(len(cfg.channel_mult))
        for i in range(cfg.num_res_blocks)
    ]


def _output_schedule(cfg: UNetConfig) -> list[tuple[int, int]]:
    """(level, i) of every output (up) block, in execution order."""
    return [
        (level, i)
        for level in reversed(range(len(cfg.channel_mult)))
        for i in range(cfg.num_res_blocks + 1)
    ]


def _skip_base(cfg: UNetConfig, level: int) -> int:
    """Index of the first skip pushed by ``level`` (skip_0 = the input conv;
    each earlier level pushed num_res_blocks skips plus one for its
    downsample)."""
    last = len(cfg.channel_mult) - 1
    return 1 + sum(
        cfg.num_res_blocks + (1 if m != last else 0) for m in range(level)
    )


def _total_skips(cfg: UNetConfig) -> int:
    return _skip_base(cfg, len(cfg.channel_mult))


class UNet2D(nn.Module):
    """forward(x NHWC, timesteps (B,), context (B,S,D), y=(B,adm) for SDXL).

    ``control`` injects ControlNet residuals (models/controlnet.py): a dict
    with ``"input"`` (one NHWC residual per skip entry, added as each skip is
    consumed — the host UNet's hs.pop() + control pop convention) and
    ``"middle"`` (added to the middle-block output). Composed models build the
    dict inside the same jit program (``apply_control``), so it never crosses
    the kwargs-partitioning boundary as a python value.

    Structured setup-style as a staged forward — prepare → input blocks →
    middle → output blocks → finalize — so the same module serves the plain
    jitted apply AND the ``PipelineSpec`` decomposition (batch==1 block
    placement and the weight-streaming executor, parallel/streaming.py). The
    carry is a flat dict: ``h``/``emb``/``context`` plus ``skip_{i}`` entries
    (the skip stack, indexed statically per cfg) and optional ``ctrl_*``
    residuals; param names are IDENTICAL to the previous inline layout, so
    checkpoints convert unchanged.
    """

    cfg: UNetConfig

    def setup(self):
        cfg = self.cfg
        ch = cfg.model_channels
        self.time_embed_0 = nn.Dense(ch * 4, dtype=cfg.dtype)
        self.time_embed_2 = nn.Dense(ch * 4, dtype=cfg.dtype)
        if cfg.adm_in_channels is not None:
            self.label_embed_0 = nn.Dense(ch * 4, dtype=cfg.dtype)
            self.label_embed_2 = nn.Dense(ch * 4, dtype=cfg.dtype)
        self.input_conv = nn.Conv(ch, (3, 3), padding=1, dtype=cfg.dtype)
        for level, mult in enumerate(cfg.channel_mult):
            out_ch = ch * mult
            for i in range(cfg.num_res_blocks):
                setattr(self, f"in_{level}_{i}_res", ResBlock(cfg, out_ch))
                if _has_attn(cfg, level):
                    setattr(
                        self, f"in_{level}_{i}_attn",
                        SpatialTransformer(
                            cfg, out_ch, cfg.transformer_depth[level]
                        ),
                    )
            if level != len(cfg.channel_mult) - 1:
                setattr(self, f"down_{level}", Downsample(cfg, out_ch))
        mid_ch = ch * cfg.channel_mult[-1]
        self.mid_res1 = ResBlock(cfg, mid_ch)
        if middle_depth(cfg) > 0:
            self.mid_attn = SpatialTransformer(cfg, mid_ch, middle_depth(cfg))
        self.mid_res2 = ResBlock(cfg, mid_ch)
        for level in range(len(cfg.channel_mult)):
            out_ch = ch * cfg.channel_mult[level]
            for i in range(cfg.num_res_blocks + 1):
                setattr(self, f"out_{level}_{i}_res", ResBlock(cfg, out_ch))
                if _has_attn(cfg, level):
                    setattr(
                        self, f"out_{level}_{i}_attn",
                        SpatialTransformer(
                            cfg, out_ch, cfg.transformer_depth[level]
                        ),
                    )
            if level != 0:
                setattr(self, f"up_{level}", Upsample(cfg, out_ch))
        self.out_norm = nn.GroupNorm(num_groups=cfg.norm_groups, dtype=cfg.dtype)
        self.out_conv = nn.Conv(
            cfg.out_channels, (3, 3), padding=1, dtype=jnp.float32
        )

    # -- staged forward (the PipelineSpec decomposition) -----------------------

    def prepare(self, x, timesteps, context=None, y=None, control=None,
                **kwargs):
        """Embeddings + input conv on the lead device; seeds the carry with
        skip_0 and flattens any ControlNet residuals into ``ctrl_*`` entries
        so the carry stays a flat dict of arrays."""
        cfg = self.cfg
        ch = cfg.model_channels
        t_emb = timestep_embedding(timesteps, ch).astype(cfg.dtype)
        emb = self.time_embed_0(t_emb)
        emb = self.time_embed_2(nn.silu(emb))
        if cfg.adm_in_channels is not None:
            if y is None:
                raise ValueError("this config requires vector conditioning `y`")
            y_emb = self.label_embed_0(y.astype(cfg.dtype))
            emb = emb + self.label_embed_2(nn.silu(y_emb))
        x = x.astype(cfg.dtype)
        if context is not None:
            context = context.astype(cfg.dtype)
        h = self.input_conv(x)
        carry = {"h": h, "emb": emb, "context": context, "skip_0": h}
        if control is not None:
            for j, res in enumerate(control.get("input") or ()):
                carry[f"ctrl_in_{j}"] = res
            mid_residuals = control.get("middle") or ()
            if mid_residuals:
                carry["ctrl_mid"] = mid_residuals[0]
        return carry

    def input_step(self, carry, level: int, i: int):
        cfg = self.cfg
        h = getattr(self, f"in_{level}_{i}_res")(carry["h"], carry["emb"])
        if _has_attn(cfg, level):
            h = getattr(self, f"in_{level}_{i}_attn")(h, carry["context"])
        out = dict(carry)
        idx = _skip_base(cfg, level) + i
        out[f"skip_{idx}"] = h
        if i == cfg.num_res_blocks - 1 and level != len(cfg.channel_mult) - 1:
            h = getattr(self, f"down_{level}")(h)
            out[f"skip_{idx + 1}"] = h
        out["h"] = h
        return out

    def middle_step(self, carry):
        cfg = self.cfg
        h = self.mid_res1(carry["h"], carry["emb"])
        if middle_depth(cfg) > 0:
            h = self.mid_attn(h, carry["context"])
        h = self.mid_res2(h, carry["emb"])
        if "ctrl_mid" in carry:
            h = h + carry["ctrl_mid"].astype(h.dtype)
        n_ctrl = sum(1 for k in carry if k.startswith("ctrl_in_"))
        n_skips = sum(1 for k in carry if k.startswith("skip_"))
        if n_ctrl and n_ctrl != n_skips:
            raise ValueError(
                f"control['input'] has {n_ctrl} residuals for "
                f"{n_skips} skip connections — ControlNet/UNet config "
                "mismatch"
            )
        return {**carry, "h": h}

    def output_step(self, carry, level: int, i: int):
        cfg = self.cfg
        # j-th output block consumes the skip stack LIFO (hs.pop() parity).
        j = (
            (len(cfg.channel_mult) - 1 - level) * (cfg.num_res_blocks + 1) + i
        )
        idx = _total_skips(cfg) - 1 - j
        out = dict(carry)
        skip = out.pop(f"skip_{idx}")
        ctrl = out.pop(f"ctrl_in_{idx}", None)
        if ctrl is not None:
            skip = skip + ctrl.astype(skip.dtype)
        h = out["h"]
        if cfg.freeu is not None:
            h, skip = _apply_freeu(cfg, h, skip)
        h = jnp.concatenate([h, skip], axis=-1)
        h = getattr(self, f"out_{level}_{i}_res")(h, out["emb"])
        if _has_attn(cfg, level):
            h = getattr(self, f"out_{level}_{i}_attn")(h, out["context"])
        if i == cfg.num_res_blocks and level != 0:
            h = getattr(self, f"up_{level}")(h)
        out["h"] = h
        return out

    def finalize(self, carry, out_shape: tuple[int, ...]):
        """Final norm + projection (lead device); ``out_shape`` is the
        PipelineSpec finalize contract — the UNet's geometry already rides
        the carry, so it is unused here."""
        del out_shape
        h = self.out_norm(carry["h"])
        h = nn.silu(h)
        return self.out_conv(h.astype(jnp.float32))

    def __call__(self, x, timesteps, context=None, y=None, control=None,
                 **kwargs):
        cfg = self.cfg
        carry = self.prepare(x, timesteps, context, y=y, control=control)
        for level, i in _input_schedule(cfg):
            carry = self.input_step(carry, level, i)
        carry = self.middle_step(carry)
        for level, i in _output_schedule(cfg):
            carry = self.output_step(carry, level, i)
        return self.finalize(carry, x.shape)


def apply_inpaint_conditioning(base: "DiffusionModel", mask, masked_latent):
    """Compose the 9-channel inpaint-model input convention into a
    DiffusionModel: every denoise step's input becomes
    ``concat([x, mask, masked_image_latent], channel)`` — the sd-inpainting
    checkpoint contract (4 + 1 + 4 channels). Like ``apply_control``, the
    conditioning channels ride the merged params pytree so the composition
    places/shards through ``parallelize`` and the whole step stays one jit
    program. ``mask`` is 1 where content is REGENERATED (latent resolution,
    (1|B, H, W, 1)); ``masked_latent`` is the VAE encode of the
    mask-blanked pixels."""
    merged = {
        "base": base.params,
        "mask": jnp.asarray(mask, jnp.float32),
        "masked": jnp.asarray(masked_latent, jnp.float32),
    }
    base_apply = base.apply

    def _bcast(a, batch):
        if a.ndim == 3:
            a = a[None]
        if a.shape[0] != batch:
            if a.shape[0] != 1:
                raise ValueError(
                    f"inpaint conditioning batch {a.shape[0]} != latent "
                    f"batch {batch}: pass ONE mask/masked-image (it "
                    "broadcasts); per-sample conditioning is not supported"
                )
            a = jnp.repeat(a, batch, axis=0)
        return a

    def apply(p, x, timesteps, context=None, **kw):
        m = _bcast(p["mask"], x.shape[0])
        ml = _bcast(p["masked"], x.shape[0])
        x_in = jnp.concatenate([x, m.astype(x.dtype), ml.astype(x.dtype)], -1)
        return base_apply(p["base"], x_in, timesteps, context, **kw)

    return DiffusionModel(
        apply=apply, params=merged, name=f"{base.name}+inpaint",
        config=base.config,
    )


def unclip_adm(tags, adm_in_channels: int, rng=None,
               merge_augmentation: float = 0.05) -> jnp.ndarray:
    """SD2.x-unCLIP adm vector from ``unCLIPConditioning`` tags: each tag's
    CLIP image embeds are noise-augmented by its ``noise_augmentation`` level
    (DDPM q_sample over the squared-cosine alpha-bar table — the host's
    CLIPEmbeddingNoiseAugmentation, whose SD21UnclipL/H noise_aug_config sets
    ``beta_schedule: squaredcos_cap_v2``; identity data stats), concatenated
    with the sinusoidal embedding of that level, weighted by ``strength``, and
    summed; multiple tags re-augment the summed embeds at
    ``merge_augmentation`` (the host's noise_augment_merge). Returns
    (1, adm_in_channels) float32 — broadcast to the latent batch by the
    caller. The uncond half of CFG gets zeros (host SD21UNCLIP.encode_adm
    semantics for untagged conditioning). Host-surface parity: the reference
    registers only its own nodes and assumes the host provides unCLIP
    conditioning (any_device_parallel.py:1473-1483)."""
    import jax

    from ..ops.basic import timestep_embedding

    if rng is None:
        rng = jax.random.key(0)
    n = 1000
    # squaredcos_cap_v2: beta_t = 1 - bar((t+1)/T)/bar(t/T), capped at 0.999,
    # with bar(s) = cos²(((s + 0.008)/1.008)·π/2).
    import numpy as _np

    _t = _np.arange(n, dtype=_np.float64)

    def _bar(s):
        return _np.cos((s + 0.008) / 1.008 * _np.pi / 2.0) ** 2

    betas = _np.clip(1.0 - _bar((_t + 1) / n) / _bar(_t / n), 0.0, 0.999)
    acp = jnp.asarray(_np.cumprod(1.0 - betas), jnp.float32)

    def augment(emb, aug: float, key):
        level = int(round((n - 1) * max(0.0, min(1.0, aug))))
        noise = jax.random.normal(key, emb.shape, jnp.float32)
        noised = (
            jnp.sqrt(acp[level]) * emb + jnp.sqrt(1.0 - acp[level]) * noise
        )
        lvl = jnp.full((emb.shape[0],), float(level), jnp.float32)
        return noised, timestep_embedding(lvl, adm_in_channels - emb.shape[-1])

    outs = []
    for i, tag in enumerate(tags):
        emb = jnp.asarray(tag["embeds"], jnp.float32)
        if emb.ndim == 1:
            emb = emb[None]
        emb = emb[:1]  # one adm vector; stock iterates embeds row-wise
        noised, lvl_emb = augment(
            emb, float(tag.get("noise_augmentation", 0.0)),
            jax.random.fold_in(rng, i),
        )
        outs.append(
            jnp.concatenate([noised, lvl_emb], axis=-1)
            * float(tag.get("strength", 1.0))
        )
    y = sum(outs)
    if len(outs) > 1:
        emb_dim = jnp.asarray(tags[0]["embeds"]).shape[-1]
        noised, lvl_emb = augment(
            y[:, :emb_dim], merge_augmentation,
            jax.random.fold_in(rng, len(outs)),
        )
        y = jnp.concatenate([noised, lvl_emb], axis=-1)
    return y


def _unet_pipeline_spec(module: "UNet2D", cfg: UNetConfig):
    """Stage decomposition of the UNet forward: embeddings/input conv on the
    lead device, one segment per input/middle/output block, final
    norm/projection on the lead. The skip connections ride the carry as
    statically-indexed ``skip_{i}`` entries, so the carry structure at every
    segment boundary is fixed per cfg — what both batch==1 block placement
    (parallel/pipeline.py) and the weight-streaming executor
    (parallel/streaming.py) need. The reference never pipelines UNets (its
    block-list walk finds no ['double_blocks', ...] name,
    any_device_parallel.py:1156-1166); the staged form here is what lets an
    SD-family model stream when its weights exceed HBM."""
    from .api import PipelineSegment, PipelineSpec

    def prepare(params, x, t, context=None, **kw):
        return module.apply(
            {"params": params}, x, t, context, method=UNet2D.prepare, **kw
        )

    def make_input(level, i):
        def fn(params, carry):
            return module.apply(
                {"params": params}, carry, level, i, method=UNet2D.input_step
            )

        return fn

    def middle(params, carry):
        return module.apply({"params": params}, carry, method=UNet2D.middle_step)

    def make_output(level, i):
        def fn(params, carry):
            return module.apply(
                {"params": params}, carry, level, i, method=UNet2D.output_step
            )

        return fn

    def finalize(params, carry, out_shape):
        return module.apply(
            {"params": params}, carry, out_shape, method=UNet2D.finalize
        )

    last = len(cfg.channel_mult) - 1
    segments = []
    for level, i in _input_schedule(cfg):
        keys = [f"in_{level}_{i}_res"]
        if _has_attn(cfg, level):
            keys.append(f"in_{level}_{i}_attn")
        if i == cfg.num_res_blocks - 1 and level != last:
            keys.append(f"down_{level}")
        segments.append(
            PipelineSegment(tuple(keys), make_input(level, i),
                            f"input[{level}.{i}]")
        )
    mid_keys = ["mid_res1", "mid_res2"]
    if middle_depth(cfg) > 0:
        mid_keys.insert(1, "mid_attn")
    segments.append(PipelineSegment(tuple(mid_keys), middle, "middle"))
    for level, i in _output_schedule(cfg):
        keys = [f"out_{level}_{i}_res"]
        if _has_attn(cfg, level):
            keys.append(f"out_{level}_{i}_attn")
        if i == cfg.num_res_blocks and level != 0:
            keys.append(f"up_{level}")
        segments.append(
            PipelineSegment(tuple(keys), make_output(level, i),
                            f"output[{level}.{i}]")
        )

    prepare_keys = ["time_embed_0", "time_embed_2", "input_conv"]
    if cfg.adm_in_channels is not None:
        prepare_keys[2:2] = ["label_embed_0", "label_embed_2"]
    return PipelineSpec(
        prepare_keys=tuple(prepare_keys),
        prepare=prepare,
        segments=tuple(segments),
        finalize_keys=("out_norm", "out_conv"),
        finalize=finalize,
    )


def build_unet(
    cfg: UNetConfig,
    rng=None,
    sample_shape=(1, 64, 64, 4),
    name="sd-unet",
    params=None,
) -> DiffusionModel:
    """Build a UNet DiffusionModel; ``params`` skips initialization (load path)."""
    module = UNet2D(cfg)
    if params is None:
        if rng is None:
            raise ValueError("need rng to initialize (or pass params=)")
        x = jnp.zeros(sample_shape, jnp.float32)
        t = jnp.zeros((sample_shape[0],), jnp.float32)
        ctx = jnp.zeros((sample_shape[0], 77, cfg.context_dim), jnp.float32)
        kwargs = {}
        if cfg.adm_in_channels is not None:
            kwargs["y"] = jnp.zeros((sample_shape[0], cfg.adm_in_channels), jnp.float32)
        params = module.init(rng, x, t, ctx, **kwargs)["params"]

    def apply(params, x, timesteps, context=None, **kw):
        return module.apply({"params": params}, x, timesteps, context, **kw)

    return DiffusionModel(
        apply=apply, params=params, name=name, config=cfg, block_lists=None,
        pipeline_spec=_unet_pipeline_spec(module, cfg),
    )
