"""AutoencoderKL (the SD/SDXL/FLUX image VAE) — flax.linen, NHWC, TPU-first.

The reference parallelizes only the diffusion network and leaves VAE encode/decode to
its host app (the ComfyUI MODEL wrapper it unwraps at any_device_parallel.py:921-930
is the bare UNet/DiT; latents in, latents out — README.md:199-208 describes the whole
pipeline in latent space). A *standalone* framework has to close that loop itself:
this module is the latents↔pixels stage, so the benchmark ladder's models produce
images without any torch runtime.

TPU-first choices: NHWC throughout (conv-friendly layout), bf16 compute with f32
params, single-head spatial attention in the mid block via the pluggable attention
backend, and a fixed-tile ``decode_tiled`` path (one compiled program reused for every
tile — no dynamic shapes) for images whose full-resolution activations would blow HBM.

Checkpoint layouts covered by models/convert_vae.py: ldm/ComfyUI
(``first_stage_model.*``) for SD1.5/SDXL, and the FLUX ``ae.safetensors`` layout
(same module names, no quant convs, z=16).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import attention_local
from ..ops.basic import UpsampleConv
from .tiling import blend_mask1d, tile_starts


@dataclasses.dataclass(frozen=True)
class VAEConfig:
    in_channels: int = 3
    z_channels: int = 4
    base_channels: int = 128
    channel_mult: tuple[int, ...] = (1, 2, 4, 4)
    num_res_blocks: int = 2
    norm_groups: int = 32
    # latent = (encode(x) - shift) * scale; decode takes latent / scale + shift.
    scaling_factor: float = 0.18215
    shift_factor: float = 0.0
    # SD-family checkpoints carry 1x1 quant/post_quant convs around the latent;
    # FLUX's ae.safetensors does not.
    use_quant_conv: bool = True
    dtype: Any = jnp.bfloat16


def sd_vae_config(**overrides) -> VAEConfig:
    """SD1.5 kl-f8 VAE (also the SD2.x shape)."""
    return dataclasses.replace(VAEConfig(), **overrides)


def sdxl_vae_config(**overrides) -> VAEConfig:
    return dataclasses.replace(VAEConfig(scaling_factor=0.13025), **overrides)


def sd3_vae_config(**overrides) -> VAEConfig:
    """SD3's 16-channel autoencoder (flux-style module names, no quant convs;
    scale/shift from the SD3 release)."""
    base = VAEConfig(
        z_channels=16,
        scaling_factor=1.5305,
        shift_factor=0.0609,
        use_quant_conv=False,
    )
    return dataclasses.replace(base, **overrides)


def flux_vae_config(**overrides) -> VAEConfig:
    """FLUX/Z-Image 16-channel autoencoder (scale/shift from the flux repo)."""
    base = VAEConfig(
        z_channels=16,
        scaling_factor=0.3611,
        shift_factor=0.1159,
        use_quant_conv=False,
    )
    return dataclasses.replace(base, **overrides)


class VAEResBlock(nn.Module):
    cfg: VAEConfig
    out_ch: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h = nn.GroupNorm(num_groups=cfg.norm_groups, dtype=cfg.dtype, name="norm1")(x)
        h = nn.silu(h)
        h = nn.Conv(self.out_ch, (3, 3), padding=1, dtype=cfg.dtype, name="conv1")(h)
        h = nn.GroupNorm(num_groups=cfg.norm_groups, dtype=cfg.dtype, name="norm2")(h)
        h = nn.silu(h)
        h = nn.Conv(self.out_ch, (3, 3), padding=1, dtype=cfg.dtype, name="conv2")(h)
        if x.shape[-1] != self.out_ch:
            x = nn.Conv(self.out_ch, (1, 1), dtype=cfg.dtype, name="nin_shortcut")(x)
        return x + h


class VAEAttnBlock(nn.Module):
    """Single-head full spatial self-attention (the kl-f8 mid-block attention)."""

    cfg: VAEConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, H, W, C = x.shape
        h = nn.GroupNorm(num_groups=cfg.norm_groups, dtype=cfg.dtype, name="norm")(x)
        q = nn.Conv(C, (1, 1), dtype=cfg.dtype, name="q")(h)
        k = nn.Conv(C, (1, 1), dtype=cfg.dtype, name="k")(h)
        v = nn.Conv(C, (1, 1), dtype=cfg.dtype, name="v")(h)
        # (B, H*W, 1 head, C) through the backend-dispatched attention.
        q, k, v = (t.reshape(B, H * W, 1, C) for t in (q, k, v))
        h = attention_local(q, k, v).reshape(B, H, W, C)
        h = nn.Conv(C, (1, 1), dtype=cfg.dtype, name="proj_out")(h)
        return x + h


class Downsample(nn.Module):
    cfg: VAEConfig

    @nn.compact
    def __call__(self, x):
        # ldm kl-f8 uses asymmetric (0,1)x(0,1) padding + VALID stride-2 conv.
        x = jnp.pad(x, ((0, 0), (0, 1), (0, 1), (0, 0)))
        return nn.Conv(
            x.shape[-1], (3, 3), strides=2, padding="VALID",
            dtype=self.cfg.dtype, name="conv",
        )(x)


class Upsample(nn.Module):
    cfg: VAEConfig

    @nn.compact
    def __call__(self, x):
        return UpsampleConv(x.shape[-1], dtype=self.cfg.dtype, name="conv")(x)


class Encoder(nn.Module):
    cfg: VAEConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h = nn.Conv(
            cfg.base_channels, (3, 3), padding=1, dtype=cfg.dtype, name="conv_in"
        )(x.astype(cfg.dtype))
        for level, mult in enumerate(cfg.channel_mult):
            ch = cfg.base_channels * mult
            for i in range(cfg.num_res_blocks):
                h = VAEResBlock(cfg, ch, name=f"down_{level}_block_{i}")(h)
            if level != len(cfg.channel_mult) - 1:
                h = Downsample(cfg, name=f"down_{level}_downsample")(h)
        h = VAEResBlock(cfg, h.shape[-1], name="mid_block_1")(h)
        h = VAEAttnBlock(cfg, name="mid_attn_1")(h)
        h = VAEResBlock(cfg, h.shape[-1], name="mid_block_2")(h)
        h = nn.GroupNorm(num_groups=cfg.norm_groups, dtype=cfg.dtype, name="norm_out")(h)
        h = nn.silu(h)
        return nn.Conv(
            2 * cfg.z_channels, (3, 3), padding=1, dtype=cfg.dtype, name="conv_out"
        )(h)


class Decoder(nn.Module):
    cfg: VAEConfig

    @nn.compact
    def __call__(self, z):
        cfg = self.cfg
        ch = cfg.base_channels * cfg.channel_mult[-1]
        h = nn.Conv(ch, (3, 3), padding=1, dtype=cfg.dtype, name="conv_in")(
            z.astype(cfg.dtype)
        )
        h = VAEResBlock(cfg, ch, name="mid_block_1")(h)
        h = VAEAttnBlock(cfg, name="mid_attn_1")(h)
        h = VAEResBlock(cfg, ch, name="mid_block_2")(h)
        for level in reversed(range(len(cfg.channel_mult))):
            ch = cfg.base_channels * cfg.channel_mult[level]
            for i in range(cfg.num_res_blocks + 1):
                h = VAEResBlock(cfg, ch, name=f"up_{level}_block_{i}")(h)
            if level != 0:
                h = Upsample(cfg, name=f"up_{level}_upsample")(h)
        h = nn.GroupNorm(num_groups=cfg.norm_groups, dtype=cfg.dtype, name="norm_out")(h)
        h = nn.silu(h)
        return nn.Conv(
            cfg.in_channels, (3, 3), padding=1, dtype=cfg.dtype, name="conv_out"
        )(h)


class AutoencoderKL(nn.Module):
    cfg: VAEConfig

    def setup(self):
        cfg = self.cfg
        self.encoder = Encoder(cfg, name="encoder")
        self.decoder = Decoder(cfg, name="decoder")
        if cfg.use_quant_conv:
            self.quant_conv = nn.Conv(
                2 * cfg.z_channels, (1, 1), dtype=cfg.dtype, name="quant_conv"
            )
            self.post_quant_conv = nn.Conv(
                cfg.z_channels, (1, 1), dtype=cfg.dtype, name="post_quant_conv"
            )

    def moments(self, x):
        """Pixels (B,H,W,3 in [-1,1]) → (mean, logvar) of the latent posterior."""
        h = self.encoder(x)
        if self.cfg.use_quant_conv:
            h = self.quant_conv(h)
        mean, logvar = jnp.split(h, 2, axis=-1)
        return mean, jnp.clip(logvar, -30.0, 20.0)

    def encode(self, x, rng=None):
        """Pixels → scaled latent. Deterministic (posterior mean) without ``rng``."""
        mean, logvar = self.moments(x)
        z = mean
        if rng is not None:
            z = mean + jnp.exp(0.5 * logvar) * jax.random.normal(
                rng, mean.shape, mean.dtype
            )
        return (z - self.cfg.shift_factor) * self.cfg.scaling_factor

    def decode(self, z):
        """Scaled latent → pixels (B, 8H, 8W, 3)."""
        z = z / self.cfg.scaling_factor + self.cfg.shift_factor
        h = z
        if self.cfg.use_quant_conv:
            h = self.post_quant_conv(h)
        return self.decoder(h)

    def __call__(self, x, rng=None):
        return self.decode(self.encode(x, rng))


def vae_output_to_images(decoded: jnp.ndarray) -> jnp.ndarray:
    """Decoder output ([-1, 1] convention) → float images in [0, 1], NHWC — the
    single owner of the output-range convention (pipelines and nodes both use it)."""
    return jnp.clip(decoded * 0.5 + 0.5, 0.0, 1.0)


def images_to_vae_input(images: jnp.ndarray) -> jnp.ndarray:
    """Float images in [0, 1] → the decoder/encoder [-1, 1] convention (inverse
    of ``vae_output_to_images``)."""
    return images * 2.0 - 1.0


def normalize_mask(mask, hw: tuple, method: str = "nearest") -> jnp.ndarray:
    """A MASK wire value in any of its shapes ((H, W) / (B, H, W) /
    (B, H, W, 1)) → float (B, H, W, 1) at the ``hw`` spatial size — the one
    mask-conditioning convention shared by the inpaint nodes (each resizes the
    SAME normalized mask to pixel and latent resolutions)."""
    import jax

    m = jnp.asarray(mask, jnp.float32)
    if m.ndim == 2:
        m = m[None]
    if m.ndim == 3:
        m = m[..., None]
    if m.shape[1:3] != tuple(hw):
        m = jax.image.resize(m, (m.shape[0], *hw, 1), method=method)
    return m


def encode_maybe_tiled(vae, x, tile: int = 0) -> jnp.ndarray:
    """Encode ``x`` through ``vae``, tiled when ``tile > 0`` — the encode-side
    owner of the tile/overlap dispatch policy: overlap = tile/4 floored to the
    VAE's spatial-factor alignment (so any factor-aligned tile size works)."""
    if tile:
        f = vae.spatial_factor
        # Floor BOTH to factor alignment: host widgets/exports carry
        # arbitrary tile sizes (stock accepts any), and encode_tiled
        # rejects unaligned values.
        tile = max(f, tile // f * f)
        # overlap must stay < tile (encode_tiled's contract): a tile floored
        # all the way down to one factor cell runs overlap-free.
        overlap = min(max(f, tile // 4 // f * f), tile - f)
        return vae.encode_tiled(x, tile=tile, overlap=max(0, overlap))
    return vae.encode(x)


def decode_maybe_tiled(vae, z, tile: int = 0) -> jnp.ndarray:
    """Decode ``z`` through ``vae`` (image VAE or VideoVAE), tiled when
    ``tile > 0`` — the single owner of the tile/overlap dispatch policy
    (overlap = tile/4) used by the pipelines and the VAE-decode node."""
    if tile:
        return vae.decode_tiled(z, tile=tile, overlap=tile // 4)
    return vae.decode(z)


@dataclasses.dataclass(frozen=True)
class VAE:
    """The VAE as data: jit-cached encode/decode + weights (mirrors
    api.DiffusionModel's jit-cache-per-entry-point shape so the node layer treats
    both uniformly). Params enter every jitted program as arguments, never as
    baked-in constants."""

    cfg: VAEConfig
    params: Any

    def _jitted(self, method):
        if not hasattr(self, "_jit_cache"):
            object.__setattr__(self, "_jit_cache", {})
        fn = self._jit_cache.get(method)
        if fn is None:
            module = AutoencoderKL(self.cfg)
            fn = self._jit_cache[method] = jax.jit(
                lambda p, *a: module.apply({"params": p}, *a, method=method)
            )
        return fn

    def _run(self, method, x, *rest):
        # A latent straight out of a device chain is still sharded over it:
        # the partitioned program must be called under that mesh.
        from ..parallel.mesh import mesh_context, sharded_mesh_of
        from .loader import residency

        residency.ensure(self.params)  # back on the chip if it was sent out
        with mesh_context(sharded_mesh_of(x)):
            return self._jitted(method)(self.params, x, *rest)

    def encode(self, x, rng=None):
        return self._run(AutoencoderKL.encode, x, rng)

    def decode(self, z):
        return self._run(AutoencoderKL.decode, z)

    @property
    def spatial_factor(self) -> int:
        """Pixels per latent cell along each spatial dim (8 for the kl-f8 family)."""
        return 2 ** (len(self.cfg.channel_mult) - 1)

    def encode_tiled(self, x, tile: int = 512, overlap: int = 128):
        """Encode in fixed-size overlapping PIXEL tiles (dims in pixels, must be
        multiples of the spatial factor), blending the latent overlaps — the
        img2img counterpart of ``decode_tiled`` for resolutions whose encoder
        activations would blow HBM. Deterministic (posterior mean) only."""
        B, H, W, _ = x.shape
        if H <= tile and W <= tile:
            return self.encode(x)
        f = self.spatial_factor
        if tile % f or overlap % f:
            raise ValueError(f"tile/overlap must be multiples of {f}")
        if not 0 <= overlap < tile:
            raise ValueError(f"need 0 <= overlap < tile, got {overlap=} {tile=}")
        encode = functools.partial(
            self._jitted(AutoencoderKL.encode), self.params
        )
        th, tw = min(tile, H), min(tile, W)
        mask = (
            blend_mask1d(th // f, overlap // f, 1)[:, None]
            * blend_mask1d(tw // f, overlap // f, 1)[None, :]
        )[None, :, :, None]
        out = np.zeros((B, H // f, W // f, self.cfg.z_channels), np.float32)
        weight = np.zeros((1, H // f, W // f, 1), np.float32)
        # Hold the full-resolution pixels on the HOST (like decode_tiled's
        # host accumulation): only one tile's pixels + encoder activations
        # live in HBM at a time.
        x_host = np.asarray(x, np.float32)
        # Window starts computed on the latent grid then scaled back up, so
        # edge tiles (which slide inward) stay f-aligned.
        hs_list = [s * f for s in tile_starts(H // f, th // f, (tile - overlap) // f)]
        ws_list = [s * f for s in tile_starts(W // f, tw // f, (tile - overlap) // f)]
        for hs in hs_list:
            for ws in ws_list:
                enc = np.asarray(
                    encode(x_host[:, hs : hs + th, ws : ws + tw, :], None),
                    np.float32,
                )
                hl, wl = hs // f, ws // f
                out[:, hl : hl + th // f, wl : wl + tw // f] += enc * mask
                weight[:, hl : hl + th // f, wl : wl + tw // f] += mask
        return jnp.asarray(out / weight)

    def decode_tiled(self, z, tile: int = 64, overlap: int = 16):
        """Decode in fixed-size overlapping latent tiles, linearly blending the
        overlaps — bounds decoder activation memory at large resolutions. A cached
        jitted program serves every tile of the same shape (at most two shapes per
        call: interior tiles plus a clamped shape when a dim is shorter than
        ``tile``); edge tiles slide the window back inside the image, never pad."""
        B, H, W, C = z.shape
        if H <= tile and W <= tile:
            return self.decode(z)
        if not 0 <= overlap < tile:
            raise ValueError(f"need 0 <= overlap < tile, got {overlap=} {tile=}")
        f = self.spatial_factor
        stride = tile - overlap
        decode = functools.partial(self._jitted(AutoencoderKL.decode), self.params)
        th, tw = min(tile, H), min(tile, W)
        mask = (
            blend_mask1d(th, overlap, f)[:, None]
            * blend_mask1d(tw, overlap, f)[None, :]
        )[None, :, :, None]
        # Accumulate on the host: the whole point of tiling is that full-resolution
        # buffers don't fit comfortably on-device; only one decoded tile lives in
        # HBM at a time, and the blend (memory-bound, not MXU work) runs in numpy.
        out = np.zeros((B, H * f, W * f, self.cfg.in_channels), np.float32)
        weight = np.zeros((1, H * f, W * f, 1), np.float32)
        for hs in tile_starts(H, th, stride):
            for ws in tile_starts(W, tw, stride):
                dec = np.asarray(
                    decode(z[:, hs : hs + th, ws : ws + tw, :]), np.float32
                )
                out[:, hs * f : (hs + th) * f, ws * f : (ws + tw) * f] += dec * mask
                weight[:, hs * f : (hs + th) * f, ws * f : (ws + tw) * f] += mask
        return jnp.asarray(out / weight)


def build_vae(cfg: VAEConfig, rng=None, params=None, sample_hw: int = 32) -> VAE:
    """Initialize (or wrap pre-converted ``params`` from convert_vae) a VAE."""
    if params is None:
        if rng is None:
            raise ValueError("need rng to initialize (or pass params=)")
        module = AutoencoderKL(cfg)
        x = jnp.zeros((1, sample_hw, sample_hw, cfg.in_channels), jnp.float32)
        params = module.init(rng, x)["params"]
    return VAE(cfg=cfg, params=params)
