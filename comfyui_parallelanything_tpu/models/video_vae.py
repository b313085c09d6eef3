"""WAN-class causal 3D video VAE — flax.linen, NTHWC, TPU-first.

The reference parallelizes the diffusion network only and leaves decode to the
host app; its WAN2.2 support (reference README.md:5 "Tested on … WAN2.2") therefore
presumes a host-side video VAE. Standalone, this module is that stage: it maps
pixel clips (B, T, H, W, 3) to latent clips (B, 1+(T-1)/4, H/8, W/8, z) and back.

Compression semantics match the WAN family: 8× spatial, 4× temporal, with the
first frame kept un-downsampled in time so a clip of T = 4k+1 frames encodes to
k+1 latent frames (a single image, T=1, encodes to one latent frame — the video
VAE subsumes the image case). All temporal convolutions are *causal* (front-
padded only), so frame t's latent never depends on frames > t.

The published decoder (``wan/modules/vae2_1.py``) walks the latent frames one at
a time, every causal convolution carrying the last two frames of its own input
from one call to the next, and its temporal up-sampler is NOT a plain causal
convolution: the first frame passes as it is, ``time_conv`` runs over the frames
after it with zeros (not the first frame) as their history, and each of those
frames becomes two. ``VideoAutoencoderKL.decode`` is that arithmetic as one
fixed-shape program over the whole clip (explicit front padding, no cache
state): its last stage holds every pixel frame at 96 channels at once, 3.8 GB a
tensor at 49 frames of 832 x 480, so it serves small clips, the tests and the
encoder's round trip. ``VideoVAE.decode`` is the same arithmetic bounded in
time: the first latent frame, then a ``lax.scan`` over the others with the
convolutions' histories as the carry (``_History``), one compiled program a
latent shape (XLA module ``jit_video_decode``) whose live set is one latent
frame's four pixel frames whatever the clip's length. Both forms give the same
frames (tests/test_wan_reference.py). ``decode_tiled`` bounds memory in space on
top of that (blended overlaps, one program a tile shape).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import attention_local
from ..ops.basic import rms_normalize, upsample2x_conv3x3
from .tiling import blend_mask1d, tile_starts

# Per-channel latent statistics of the WAN 16-channel VAE (the published
# normalization constants; latents are stored as (z - mean) / std).
WAN_LATENT_MEAN = (
    -0.7571, -0.7089, -0.9113, 0.1075, -0.1745, 0.9653, -0.1517, 1.5508,
    0.4134, -0.0715, 0.5517, -0.3632, -0.1922, -0.9497, 0.2503, -0.2921,
)
WAN_LATENT_STD = (
    2.8184, 1.4541, 2.3275, 2.6558, 1.2196, 1.7708, 2.6052, 2.0743,
    3.2687, 2.1526, 2.8652, 1.5579, 1.6382, 1.1253, 2.8251, 1.9160,
)


@dataclasses.dataclass(frozen=True)
class VideoVAEConfig:
    in_channels: int = 3
    z_channels: int = 16
    base_channels: int = 96
    channel_mult: tuple[int, ...] = (1, 2, 4, 4)
    num_res_blocks: int = 2
    # Per non-final level: does the downsample at the end of this level also
    # halve time? (False, True, True) → spatial 8x, temporal 4x.
    temporal_downsample: tuple[bool, ...] = (False, True, True)
    latent_mean: tuple[float, ...] = WAN_LATENT_MEAN
    latent_std: tuple[float, ...] = WAN_LATENT_STD
    dtype: Any = jnp.bfloat16

    @property
    def spatial_factor(self) -> int:
        return 2 ** (len(self.channel_mult) - 1)

    @property
    def temporal_factor(self) -> int:
        return 2 ** sum(self.temporal_downsample)

    def latent_frames(self, t: int) -> int:
        """Pixel frames → latent frames (first frame never merged)."""
        f = self.temporal_factor
        if (t - 1) % f:
            raise ValueError(f"frame count must be 1 mod {f}, got {t}")
        return 1 + (t - 1) // f


def wan_vae_config(**overrides) -> VideoVAEConfig:
    return dataclasses.replace(VideoVAEConfig(), **overrides)


class _RMSNormC(nn.Module):
    """Channel-wise RMS norm over the last axis (WAN's `F.normalize * √C * γ`
    form is algebraically this), optional bias for the attention-block variant."""

    use_bias: bool = False

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        gamma = self.param("scale", nn.initializers.ones, (c,))
        y = rms_normalize(x, gamma, eps=1e-12)
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros, (c,))
            y = (y.astype(jnp.float32) + bias).astype(x.dtype)
        return y


class _History:
    """The carried state of the time-bounded decode: each causal convolution's
    last ``kt - 1`` input frames, in call order. ``carried`` None is the first
    latent frame, whose history is zeros (the same front padding the
    whole-clip program writes); the convolutions leave what the next latent
    frame needs in ``kept``, which the scan hands back as ``carried``."""

    def __init__(self, carried=None):
        self.first = carried is None
        self._carried = list(carried or ())
        self.kept: list = []

    def front(self, x, n: int):
        """The ``n`` frames in front of ``x`` (B, T, H, W, C)."""
        if self.first:
            return jnp.zeros(x.shape[:1] + (n,) + x.shape[2:], x.dtype)
        return self._carried[len(self.kept)]

    def keep(self, frames):
        self.kept.append(frames)


class CausalConv3d(nn.Module):
    """3D conv on NTHWC with causal (front-only) time padding and SAME spatial
    padding. With time stride s and kernel kt, front pad kt-1 gives
    T → (T-1)//s + 1 — exactly the first-frame-preserving schedule. Under a
    ``history`` (the time-bounded decode) the front is the carried frames of
    this convolution's own input instead of zeros."""

    features: int
    kernel: tuple[int, int, int] = (3, 3, 3)
    strides: tuple[int, int, int] = (1, 1, 1)
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, history: _History | None = None):
        kt, kh, kw = self.kernel
        pad_t = kt - 1
        if history is not None and pad_t:
            x = jnp.concatenate([history.front(x, pad_t).astype(x.dtype), x], axis=1)
            history.keep(x[:, -pad_t:])
            pad_t = 0
        x = jnp.pad(
            x,
            (
                (0, 0),
                (pad_t, 0),
                (kh // 2, kh // 2),
                (kw // 2, kw // 2),
                (0, 0),
            ),
        )
        return nn.Conv(
            self.features, self.kernel, strides=self.strides, padding="VALID",
            dtype=self.dtype, name="conv",
        )(x)


class VideoResBlock(nn.Module):
    cfg: VideoVAEConfig
    out_ch: int

    @nn.compact
    def __call__(self, x, history: _History | None = None):
        cfg = self.cfg
        h = _RMSNormC(name="norm1")(x)
        h = nn.silu(h)
        h = CausalConv3d(self.out_ch, dtype=cfg.dtype, name="conv1")(h, history)
        h = _RMSNormC(name="norm2")(h)
        h = nn.silu(h)
        h = CausalConv3d(self.out_ch, dtype=cfg.dtype, name="conv2")(h, history)
        if x.shape[-1] != self.out_ch:
            x = CausalConv3d(
                self.out_ch, kernel=(1, 1, 1), dtype=cfg.dtype, name="shortcut"
            )(x)
        return x + h


class FrameAttnBlock(nn.Module):
    """Per-frame 2D single-head spatial attention (the mid-block attention);
    frames fold into the batch so time never mixes here."""

    cfg: VideoVAEConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, H, W, C = x.shape
        h = _RMSNormC(use_bias=True, name="norm")(x)
        qkv = nn.Conv(3 * C, (1, 1, 1), dtype=cfg.dtype, name="to_qkv")(h)
        q, k, v = jnp.split(qkv.reshape(B * T, H * W, 1, 3 * C), 3, axis=-1)
        h = attention_local(q, k, v).reshape(B, T, H, W, C)
        h = nn.Conv(C, (1, 1, 1), dtype=cfg.dtype, name="proj")(h)
        return x + h


class SpatialDownsample(nn.Module):
    """(0,1)×(0,1) zero pad + stride-2 VALID conv on H,W (frame-local)."""

    cfg: VideoVAEConfig
    temporal: bool = False

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        h = jnp.pad(x, ((0, 0), (0, 0), (0, 1), (0, 1), (0, 0)))
        h = nn.Conv(
            c, (1, 3, 3), strides=(1, 2, 2), padding="VALID",
            dtype=self.cfg.dtype, name="conv",
        )(h)
        if self.temporal:
            # Causal stride-2 time conv: front pad 2, kernel 3 → (T-1)//2 + 1.
            h = CausalConv3d(
                c, kernel=(3, 1, 1), strides=(2, 1, 1),
                dtype=self.cfg.dtype, name="time_conv",
            )(h)
        return h


class _FrameUpsampleConv(nn.Module):
    """``nn.Conv(features, (1, 3, 3), padding=(0, 1, 1))`` on the nearest ×2
    (H, W) upsample of every frame, through ``ops.basic.upsample2x_conv3x3``
    frame by frame: the upsampled clip is never made. The parameters are that
    convolution's (``kernel`` (1, 3, 3, C, features), ``bias``), so the
    checkpoint keys stay."""

    features: int
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        B, T, H, W, C = x.shape
        kernel = self.param(
            "kernel", nn.linear.default_kernel_init, (1, 3, 3, C, self.features))
        bias = self.param("bias", nn.initializers.zeros_init(), (self.features,))
        y = upsample2x_conv3x3(
            x.reshape(B * T, H, W, C), kernel[0], bias, self.dtype)
        return y.reshape(B, T, 2 * H, 2 * W, self.features)


def _two_frames(h):
    """(B, T, H, W, 2C) → (B, 2T, H, W, C): each frame's two halves of the
    channels become two frames, in order."""
    B, T, H, W, C2 = h.shape
    return (
        h.reshape(B, T, H, W, 2, C2 // 2)
        .transpose(0, 1, 4, 2, 3, 5)
        .reshape(B, 2 * T, H, W, C2 // 2)
    )


class SpatialUpsample(nn.Module):
    """Nearest 2× on H,W + 3×3 conv halving channels. In temporal mode time
    doubles BEFORE that, by the published rule (``Resample.forward``,
    ``upsample3d``): the clip's first frame passes as it is; ``time_conv``
    runs over the frames after it, with zeros — not the first frame — as
    their history, and each of them becomes two. T latent-side frames →
    2T−1 (inverse of the causal downsample schedule)."""

    cfg: VideoVAEConfig
    temporal: bool = False

    @nn.compact
    def __call__(self, x, history: _History | None = None):
        cfg = self.cfg
        C = x.shape[-1]
        if self.temporal:
            time_conv = CausalConv3d(
                2 * C, kernel=(3, 1, 1), dtype=cfg.dtype, name="time_conv"
            )
            if history is None:
                rest = x[:, 1:]
                if rest.shape[1]:
                    x = jnp.concatenate(
                        [x[:, :1], _two_frames(time_conv(rest))], axis=1
                    )
            elif history.first:
                # The clip's first frame: as it is, and zeros in front of the
                # frame after it.
                history.keep(jnp.zeros(x.shape[:1] + (2,) + x.shape[2:], x.dtype))
            else:
                x = _two_frames(time_conv(x, history))
        return _FrameUpsampleConv(C // 2, dtype=cfg.dtype, name="conv")(x)


class VideoEncoder(nn.Module):
    cfg: VideoVAEConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h = CausalConv3d(cfg.base_channels, dtype=cfg.dtype, name="conv_in")(
            x.astype(cfg.dtype)
        )
        for level, mult in enumerate(cfg.channel_mult):
            ch = cfg.base_channels * mult
            for i in range(cfg.num_res_blocks):
                h = VideoResBlock(cfg, ch, name=f"down_{level}_block_{i}")(h)
            if level != len(cfg.channel_mult) - 1:
                h = SpatialDownsample(
                    cfg, temporal=cfg.temporal_downsample[level],
                    name=f"down_{level}_downsample",
                )(h)
        h = VideoResBlock(cfg, h.shape[-1], name="mid_block_1")(h)
        h = FrameAttnBlock(cfg, name="mid_attn_1")(h)
        h = VideoResBlock(cfg, h.shape[-1], name="mid_block_2")(h)
        h = _RMSNormC(name="norm_out")(h)
        h = nn.silu(h)
        return CausalConv3d(2 * cfg.z_channels, dtype=cfg.dtype, name="conv_out")(h)


class VideoDecoder(nn.Module):
    """Mirror of the encoder. Channel plan follows the WAN decoder: each
    upsample halves channels, so the first block of every post-upsample level
    re-expands from half the previous level's width. ``history``: the
    time-bounded decode's carried state (``_History``); None is the whole
    clip at once."""

    cfg: VideoVAEConfig

    @nn.compact
    def __call__(self, z, history: _History | None = None):
        cfg = self.cfg
        ch = cfg.base_channels * cfg.channel_mult[-1]
        h = CausalConv3d(ch, dtype=cfg.dtype, name="conv_in")(
            z.astype(cfg.dtype), history)
        h = VideoResBlock(cfg, ch, name="mid_block_1")(h, history)
        h = FrameAttnBlock(cfg, name="mid_attn_1")(h)
        h = VideoResBlock(cfg, ch, name="mid_block_2")(h, history)
        temporal_up = tuple(reversed(cfg.temporal_downsample))
        n = len(cfg.channel_mult)
        for j, level in enumerate(reversed(range(n))):
            ch = cfg.base_channels * cfg.channel_mult[level]
            for i in range(cfg.num_res_blocks + 1):
                h = VideoResBlock(cfg, ch, name=f"up_{level}_block_{i}")(h, history)
            if j != n - 1:
                h = SpatialUpsample(
                    cfg, temporal=temporal_up[j], name=f"up_{level}_upsample"
                )(h, history)
        h = _RMSNormC(name="norm_out")(h)
        h = nn.silu(h)
        return CausalConv3d(cfg.in_channels, dtype=cfg.dtype, name="conv_out")(
            h, history)


class VideoAutoencoderKL(nn.Module):
    cfg: VideoVAEConfig

    def setup(self):
        cfg = self.cfg
        self.encoder = VideoEncoder(cfg, name="encoder")
        self.decoder = VideoDecoder(cfg, name="decoder")
        self.quant_conv = CausalConv3d(
            2 * cfg.z_channels, kernel=(1, 1, 1), dtype=cfg.dtype, name="quant_conv"
        )
        self.post_quant_conv = CausalConv3d(
            cfg.z_channels, kernel=(1, 1, 1), dtype=cfg.dtype, name="post_quant_conv"
        )

    def moments(self, x):
        h = self.quant_conv(self.encoder(x))
        mean, logvar = jnp.split(h, 2, axis=-1)
        return mean, jnp.clip(logvar, -30.0, 20.0)

    def encode(self, x, rng=None):
        """Clip (B,T,H,W,3 in [-1,1], T ≡ 1 mod temporal_factor) → normalized
        latent (B, 1+(T-1)/tf, H/8, W/8, z). Posterior mean unless ``rng``."""
        mean, logvar = self.moments(x)
        z = mean
        if rng is not None:
            z = mean + jnp.exp(0.5 * logvar) * jax.random.normal(
                rng, mean.shape, mean.dtype
            )
        mu = jnp.asarray(self.cfg.latent_mean, z.dtype)
        sd = jnp.asarray(self.cfg.latent_std, z.dtype)
        return (z - mu) / sd

    def decode(self, z, history: _History | None = None):
        """Normalized latent clip → pixel clip in [-1, 1] convention, the
        whole clip in one pass; with ``history`` the latent frames ``z`` holds
        continue the clip whose state it carries (``VideoVAE.decode``)."""
        mu = jnp.asarray(self.cfg.latent_mean, z.dtype)
        sd = jnp.asarray(self.cfg.latent_std, z.dtype)
        return self.decoder(self.post_quant_conv(z * sd + mu), history)

    def __call__(self, x, rng=None):
        return self.decode(self.encode(x, rng))


@dataclasses.dataclass(frozen=True)
class VideoVAE:
    """Video VAE as data: jit-cached encode/decode + weights (same shape as
    models.vae.VAE so nodes/pipelines treat image and video VAEs uniformly)."""

    cfg: VideoVAEConfig
    params: Any

    def _jitted(self, method):
        if not hasattr(self, "_jit_cache"):
            object.__setattr__(self, "_jit_cache", {})
        fn = self._jit_cache.get(method)
        if fn is None:
            module = VideoAutoencoderKL(self.cfg)
            fn = self._jit_cache[method] = jax.jit(
                lambda p, *a: module.apply({"params": p}, *a, method=method)
            )
        return fn

    def _resident_params(self):
        from .loader import residency

        residency.ensure(self.params)  # back on the chip if it was sent out
        return self.params

    def encode(self, x, rng=None):
        return self._jitted(VideoAutoencoderKL.encode)(
            self._resident_params(), x, rng)

    def _decode_program(self, params, z):
        """The decode bounded in time, one program a latent shape, compiled
        where it is first asked for so that what it needs beside the
        parameters is known before it runs: the clip's first latent frame
        through the decoder with zeros as every convolution's history, then a
        ``lax.scan`` over the other latent frames with the histories as the
        carry — four pixel frames a scan step, the same arithmetic as
        ``VideoAutoencoderKL.decode`` on the whole clip."""
        if not hasattr(self, "_decode_compiled"):
            object.__setattr__(self, "_decode_compiled", {})
        key = (z.shape, z.dtype, getattr(z, "sharding", None))
        program = self._decode_compiled.get(key)
        if program is not None:
            return program
        module = VideoAutoencoderKL(self.cfg)

        def video_decode(params, z):
            from ..utils.metrics import registry

            B, T = z.shape[:2]
            registry.counter(
                "pa_video_decode_total",
                labels={"frames": str(self.cfg.temporal_factor * (T - 1) + 1),
                        "form": "scan" if T > 1 else "frame"},
                help="video decodes by pixel frames and form, counted once a "
                     "trace like pa_upsample_conv_total (scan: the first latent "
                     "frame, then a scan over the others with carried "
                     "convolution histories; frame: a one-frame clip)",
            )

            def run(z_t, carried):
                history = _History(carried)
                frames = module.apply(
                    {"params": params}, z_t, history,
                    method=VideoAutoencoderKL.decode,
                )
                return frames, tuple(history.kept)

            first, state = run(z[:, :1], None)
            if T == 1:
                return first

            def step(state, z_t):
                frames, state = run(z_t[:, None], state)
                return state, frames

            _, rest = jax.lax.scan(step, state, jnp.moveaxis(z[:, 1:], 1, 0))
            rest = jnp.moveaxis(rest, 0, 1)  # (B, T-1, frames a step, H, W, C)
            rest = rest.reshape((B, -1) + rest.shape[3:])
            return jnp.concatenate([first, rest], axis=1)

        program = jax.jit(video_decode).lower(params, z).compile()
        self._decode_compiled[key] = program
        return program

    def decode(self, z):
        """Normalized latent clip (B, T, H/8, W/8, z) → pixel clip
        (B, 4(T−1)+1, H, W, 3), memory bounded in time (``_decode_program``);
        an image latent (B, H/8, W/8, z) → the image (B, H, W, 3).
        The program's temporaries (6 GB at 13 x 60 x 104) are asked of the
        loader's residency rule like a load's bytes, before it runs."""
        from .loader import residency

        z = jnp.asarray(z)
        if z.ndim == 4:
            # An IMAGE latent (B, H/8, W/8, z): a clip of one frame, which the
            # causal decoder takes by its first-frame path (Qwen-Image's
            # autoencoder is this architecture on one frame) → (B, H, W, 3).
            return self.decode(z[:, None])[:, 0]
        params = self._resident_params()
        program = self._decode_program(params, z)
        residency.ensure(params, beside=program.memory_analysis().temp_size_in_bytes)
        return program(params, z)

    @property
    def spatial_factor(self) -> int:
        return self.cfg.spatial_factor

    @property
    def temporal_factor(self) -> int:
        return self.cfg.temporal_factor

    def decode_tiled(self, z, tile: int = 32, overlap: int = 8):
        """Spatially tiled decode with linear overlap blending (time stays
        whole — temporal convs are causal along an axis tiling never cuts)."""
        B, T, H, W, C = z.shape
        if H <= tile and W <= tile:
            return self.decode(z)
        if not 0 <= overlap < tile:
            raise ValueError(f"need 0 <= overlap < tile, got {overlap=} {tile=}")
        f = self.spatial_factor
        t_out = self.cfg.temporal_factor * (T - 1) + 1
        stride = tile - overlap
        decode = self.decode
        th, tw = min(tile, H), min(tile, W)
        mask = (
            blend_mask1d(th, overlap, f)[:, None]
            * blend_mask1d(tw, overlap, f)[None, :]
        )[None, None, :, :, None]
        out = np.zeros((B, t_out, H * f, W * f, self.cfg.in_channels), np.float32)
        weight = np.zeros((1, 1, H * f, W * f, 1), np.float32)
        for hs in tile_starts(H, th, stride):
            for ws in tile_starts(W, tw, stride):
                dec = np.asarray(
                    decode(z[:, :, hs : hs + th, ws : ws + tw, :]), np.float32
                )
                out[:, :, hs * f : (hs + th) * f, ws * f : (ws + tw) * f] += dec * mask
                weight[:, :, hs * f : (hs + th) * f, ws * f : (ws + tw) * f] += mask
        return jnp.asarray(out / weight)


def build_video_vae(
    cfg: VideoVAEConfig, rng=None, params=None, sample_thw=(5, 16, 16)
) -> VideoVAE:
    """Initialize (or wrap pre-converted ``params``) a video VAE."""
    if params is None:
        if rng is None:
            raise ValueError("need rng to initialize (or pass params=)")
        module = VideoAutoencoderKL(cfg)
        t, h, w = sample_thw
        x = jnp.zeros((1, t, h, w, cfg.in_channels), jnp.float32)
        params = module.init(rng, x)["params"]
    return VideoVAE(cfg=cfg, params=params)
