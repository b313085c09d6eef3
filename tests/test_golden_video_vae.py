"""WAN video VAE golden parity vs a minimal torch reference (official layout).

The torch reference below follows the public Wan2.1 causal 3D VAE design in its
non-streaming single-clip form: causal (front-padded) 3D convs, channel RMS norms
(``F.normalize·√C·γ``), per-frame single-head mid attention, (0,1)×(0,1)-padded
stride-2 spatial resampling, and the 2×-channel time conv whose halves interleave
along time on upsampling (the published first-frame rule: the first frame passes
as it is, the frames after it are convolved with zeros as their history). Exported in the official
``encoder.downsamples.{seq}`` / ``decoder.upsamples.{seq}`` flat-Sequential key
layout and converted with ``convert_wan_vae.py``.

The official torch implementation streams 4-frame chunks through per-conv caches;
this reference computes the same causal math whole-clip (the repo's documented
equivalence, convert_wan_vae.py module docstring) — so this test validates the
conv/norm/resample architecture and the converter's layout map, which round-trip
inversion (test_convert_wan.py) cannot.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_parallelanything_tpu.models.convert_wan_vae import (
    convert_wan_vae_checkpoint,
)
from comfyui_parallelanything_tpu.models.video_vae import (
    VideoAutoencoderKL,
    VideoVAEConfig,
)

torch = pytest.importorskip("torch")
tnn = torch.nn
F = torch.nn.functional

CFG = dataclasses.replace(
    VideoVAEConfig(),
    z_channels=4,
    base_channels=16,
    channel_mult=(1, 2, 2),
    num_res_blocks=1,
    temporal_downsample=(False, True),
    latent_mean=(0.0,) * 4,
    latent_std=(1.0,) * 4,
    dtype=jnp.float32,
)


class TCausalConv3d(tnn.Conv3d):
    """Conv3d with causal time padding (kt-1 front) and SAME spatial padding."""

    def forward(self, x):
        kt, kh, kw = self.kernel_size
        x = F.pad(x, (kw // 2, kw // 2, kh // 2, kh // 2, kt - 1, 0))
        return super().forward(x)


class TRMSNorm(tnn.Module):
    def __init__(self, dim, images=False, bias=False):
        super().__init__()
        shape = (dim, 1, 1) if images else (dim, 1, 1, 1)
        self.dim = dim
        self.gamma = tnn.Parameter(torch.randn(shape))
        if bias:
            self.bias = tnn.Parameter(torch.randn(shape))

    def forward(self, x):
        y = F.normalize(x.float(), dim=1) * np.sqrt(self.dim) * self.gamma
        if hasattr(self, "bias"):
            y = y + self.bias
        return y


class TResidualBlock(tnn.Module):
    def __init__(self, in_ch, out_ch):
        super().__init__()
        self.residual = tnn.Sequential(
            TRMSNorm(in_ch), tnn.SiLU(),
            TCausalConv3d(in_ch, out_ch, 3),
            TRMSNorm(out_ch), tnn.SiLU(), tnn.Identity(),
            TCausalConv3d(out_ch, out_ch, 3),
        )
        self.shortcut = (
            TCausalConv3d(in_ch, out_ch, 1) if in_ch != out_ch else tnn.Identity()
        )

    def forward(self, x):
        return self.shortcut(x) + self.residual(x)


class TAttentionBlock(tnn.Module):
    """Per-frame single-head spatial attention (frames fold into batch)."""

    def __init__(self, ch):
        super().__init__()
        self.norm = TRMSNorm(ch, images=True)
        self.to_qkv = tnn.Conv2d(ch, 3 * ch, 1)
        self.proj = tnn.Conv2d(ch, ch, 1)

    def forward(self, x):
        b, c, t, hh, ww = x.shape
        h = x.permute(0, 2, 1, 3, 4).reshape(b * t, c, hh, ww)
        qkv = self.to_qkv(self.norm(h))
        q, k, v = qkv.reshape(b * t, 3 * c, hh * ww).chunk(3, dim=1)
        logits = torch.einsum("bcq,bck->bqk", q.float(), k.float()) / np.sqrt(c)
        w = torch.softmax(logits, dim=-1)
        o = torch.einsum("bqk,bck->bcq", w, v.float()).reshape(b * t, c, hh, ww)
        o = self.proj(o)
        return x + o.reshape(b, t, c, hh, ww).permute(0, 2, 1, 3, 4)


class TDownsample(tnn.Module):
    def __init__(self, ch, temporal):
        super().__init__()
        self.temporal = temporal
        self.resample = tnn.Sequential(
            tnn.ZeroPad2d((0, 1, 0, 1)), tnn.Conv2d(ch, ch, 3, stride=2)
        )
        if temporal:
            self.time_conv = TCausalConv3d(ch, ch, (3, 1, 1), stride=(2, 1, 1))

    def forward(self, x):
        b, c, t, hh, ww = x.shape
        h = x.permute(0, 2, 1, 3, 4).reshape(b * t, c, hh, ww)
        h = self.resample(h)
        hh2, ww2 = h.shape[-2:]
        h = h.reshape(b, t, c, hh2, ww2).permute(0, 2, 1, 3, 4)
        if self.temporal:
            h = self.time_conv(h)
        return h


class TUpsample(tnn.Module):
    def __init__(self, ch, temporal):
        super().__init__()
        self.temporal = temporal
        self.resample = tnn.Sequential(
            tnn.Upsample(scale_factor=(2.0, 2.0), mode="nearest"),
            tnn.Conv2d(ch, ch // 2, 3, padding=1),
        )
        if temporal:
            self.time_conv = TCausalConv3d(ch, 2 * ch, (3, 1, 1))

    def forward(self, x):
        b, c, t, hh, ww = x.shape
        if self.temporal:
            # The published rule (Resample.forward, upsample3d): the first
            # frame passes as it is; time_conv runs over the frames after it
            # with zeros as their history; each of those becomes two.
            first, rest = x[:, :, :1], x[:, :, 1:]
            if t > 1:
                h = self.time_conv(rest)  # (b, 2c, t-1, hh, ww)
                h = h.reshape(b, 2, c, t - 1, hh, ww)
                h = torch.stack((h[:, 0], h[:, 1]), dim=3)  # (b, c, t-1, 2, ...)
                x = torch.cat([first, h.reshape(b, c, 2 * (t - 1), hh, ww)], 2)
            t = 2 * t - 1
        h = x.permute(0, 2, 1, 3, 4).reshape(b * t, c, hh, ww)
        h = self.resample(h)
        return h.reshape(b, t, c // 2, 2 * hh, 2 * ww).permute(0, 2, 1, 3, 4)


class TEncoder(tnn.Module):
    def __init__(self, cfg: VideoVAEConfig):
        super().__init__()
        chans = [cfg.base_channels * m for m in cfg.channel_mult]
        self.conv1 = TCausalConv3d(cfg.in_channels, cfg.base_channels, 3)
        downs = []
        ch = cfg.base_channels
        for level, out_ch in enumerate(chans):
            for _ in range(cfg.num_res_blocks):
                downs.append(TResidualBlock(ch, out_ch))
                ch = out_ch
            if level != len(chans) - 1:
                downs.append(TDownsample(ch, cfg.temporal_downsample[level]))
        self.downsamples = tnn.Sequential(*downs)
        self.middle = tnn.Sequential(
            TResidualBlock(ch, ch), TAttentionBlock(ch), TResidualBlock(ch, ch)
        )
        self.head = tnn.Sequential(
            TRMSNorm(ch), tnn.SiLU(), TCausalConv3d(ch, 2 * cfg.z_channels, 3)
        )

    def forward(self, x):
        return self.head(self.middle(self.downsamples(self.conv1(x))))


class TDecoder(tnn.Module):
    def __init__(self, cfg: VideoVAEConfig):
        super().__init__()
        chans = [cfg.base_channels * m for m in cfg.channel_mult]
        n = len(chans)
        ch = chans[-1]
        self.conv1 = TCausalConv3d(cfg.z_channels, ch, 3)
        self.middle = tnn.Sequential(
            TResidualBlock(ch, ch), TAttentionBlock(ch), TResidualBlock(ch, ch)
        )
        temporal_up = tuple(reversed(cfg.temporal_downsample))
        ups = []
        for j, level in enumerate(reversed(range(n))):
            out_ch = chans[level]
            for _ in range(cfg.num_res_blocks + 1):
                ups.append(TResidualBlock(ch, out_ch))
                ch = out_ch
            if j != n - 1:
                ups.append(TUpsample(ch, temporal_up[j]))
                ch = ch // 2
        self.upsamples = tnn.Sequential(*ups)
        self.head = tnn.Sequential(
            TRMSNorm(chans[0]), tnn.SiLU(),
            TCausalConv3d(chans[0], cfg.in_channels, 3),
        )

    def forward(self, z):
        return self.head(self.upsamples(self.middle(self.conv1(z))))


class TWanVAE(tnn.Module):
    def __init__(self, cfg: VideoVAEConfig):
        super().__init__()
        self.encoder = TEncoder(cfg)
        self.decoder = TDecoder(cfg)
        self.conv1 = TCausalConv3d(2 * cfg.z_channels, 2 * cfg.z_channels, 1)
        self.conv2 = TCausalConv3d(cfg.z_channels, cfg.z_channels, 1)


@pytest.fixture(scope="module")
def pair():
    torch.manual_seed(11)
    tvae = TWanVAE(CFG).eval()
    sd = {k: v.detach() for k, v in tvae.state_dict().items()}
    params = convert_wan_vae_checkpoint(sd, CFG)
    return tvae, params


def test_video_encoder_moments_golden_parity(pair):
    tvae, params = pair
    rng = np.random.default_rng(41)
    x = rng.uniform(-1, 1, size=(1, 5, 16, 16, 3)).astype(np.float32)  # NTHWC
    with torch.no_grad():
        h = tvae.conv1(
            tvae.encoder(torch.from_numpy(x.transpose(0, 4, 1, 2, 3)))
        ).numpy().transpose(0, 2, 3, 4, 1)
    want_mean = np.split(h, 2, axis=-1)[0]
    mean, _ = VideoAutoencoderKL(CFG).apply(
        {"params": params}, jnp.asarray(x), method=VideoAutoencoderKL.moments
    )
    assert mean.shape == (1, 3, 4, 4, CFG.z_channels)  # T: 5 → 3 (one temporal /2)
    np.testing.assert_allclose(np.asarray(mean), want_mean, rtol=1e-3, atol=1e-3)


def test_video_decoder_golden_parity(pair):
    tvae, params = pair
    rng = np.random.default_rng(43)
    z = rng.normal(size=(1, 3, 4, 4, CFG.z_channels)).astype(np.float32)
    with torch.no_grad():
        want = tvae.decoder(
            tvae.conv2(torch.from_numpy(z.transpose(0, 4, 1, 2, 3)))
        ).numpy().transpose(0, 2, 3, 4, 1)
    got = np.asarray(
        VideoAutoencoderKL(CFG).apply(
            {"params": params}, jnp.asarray(z), method=VideoAutoencoderKL.decode
        )
    )
    assert got.shape == (1, 5, 16, 16, 3)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)
