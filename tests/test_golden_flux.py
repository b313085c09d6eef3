"""FLUX golden parity vs a minimal torch reference implementation.

Round-trip converter tests (test_convert.py) validate layout transposes but cannot
catch an architectural misreading — wrong norm order, wrong modulation split, wrong
RoPE pairing. This applies the text-encoder strategy (test_text_encoders.py) to the
diffusion core: a from-scratch torch implementation of the FLUX architecture (the
public BFL design: double img/txt streams with joint attention, fused single blocks,
adaLN modulation, multi-axis interleaved RoPE, tanh-approx GELU, eps=1e-6 norms),
randomly initialized, exported in the official flux1-dev state-dict layout, run
through ``convert_flux_checkpoint``, and compared activation-for-activation against
``models/flux.py``.

The torch modules here are written against the publicly documented architecture —
the reference node pack contains no model code at all (it wraps ComfyUI's), so this
is the ground truth a user's checkpoint actually follows.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_parallelanything_tpu.models.convert import convert_flux_checkpoint
from comfyui_parallelanything_tpu.models.flux import FluxConfig, build_flux

torch = pytest.importorskip("torch")
tnn = torch.nn
F = torch.nn.functional

CFG = FluxConfig(
    in_channels=16,
    hidden_size=64,
    num_heads=4,          # head_dim 16
    depth=1,
    depth_single_blocks=2,
    mlp_ratio=4.0,
    context_in_dim=32,
    vec_in_dim=24,
    axes_dim=(4, 6, 6),   # sums to head_dim
    theta=10000.0,
    guidance_embed=True,
    patch_size=2,
    dtype=jnp.float32,
)


# ---------------------------------------------------------------------------------
# Torch reference (official FLUX architecture, official state-dict key layout)
# ---------------------------------------------------------------------------------


class TRMSNorm(tnn.Module):
    def __init__(self, dim):
        super().__init__()
        self.scale = tnn.Parameter(torch.randn(dim))

    def forward(self, x):
        x32 = x.float()
        n = x32 * torch.rsqrt(x32.pow(2).mean(-1, keepdim=True) + 1e-6)
        return n * self.scale


class TQKNorm(tnn.Module):
    def __init__(self, dim):
        super().__init__()
        self.query_norm = TRMSNorm(dim)
        self.key_norm = TRMSNorm(dim)


class TSelfAttention(tnn.Module):
    """Key container: .qkv / .norm.{query,key}_norm.scale / .proj."""

    def __init__(self, h, heads):
        super().__init__()
        self.qkv = tnn.Linear(h, 3 * h)
        self.norm = TQKNorm(h // heads)
        self.proj = tnn.Linear(h, h)


class TModulation(tnn.Module):
    def __init__(self, h, n_sets):
        super().__init__()
        self.lin = tnn.Linear(h, 3 * n_sets * h)
        self.n_chunks = 3 * n_sets

    def forward(self, vec):
        out = self.lin(F.silu(vec.float()))[:, None, :]
        return out.chunk(self.n_chunks, dim=-1)


class TMLPEmbedder(tnn.Module):
    def __init__(self, in_dim, h):
        super().__init__()
        self.in_layer = tnn.Linear(in_dim, h)
        self.out_layer = tnn.Linear(h, h)

    def forward(self, x):
        return self.out_layer(F.silu(self.in_layer(x)))


def t_timestep_embedding(t, dim, time_factor=1000.0, max_period=10000.0):
    t = time_factor * t.float()
    half = dim // 2
    freqs = torch.exp(
        -np.log(max_period) * torch.arange(half, dtype=torch.float32) / half
    )
    args = t[:, None] * freqs[None, :]
    return torch.cat([torch.cos(args), torch.sin(args)], dim=-1)


def t_rope_freqs(ids, axes_dim, theta):
    cos_parts, sin_parts = [], []
    for i, dim in enumerate(axes_dim):
        half = dim // 2
        freqs = theta ** (-torch.arange(half, dtype=torch.float32) / half)
        angles = ids[..., i].float()[..., None] * freqs
        cos_parts.append(torch.cos(angles))
        sin_parts.append(torch.sin(angles))
    return torch.cat(cos_parts, dim=-1), torch.cat(sin_parts, dim=-1)


def t_apply_rope(x, cos, sin):
    # (B, S, H, D), interleaved pairs; cos/sin (B, S, D//2) broadcast over heads.
    b, s, h, d = x.shape
    xp = x.float().reshape(b, s, h, d // 2, 2)
    xe, xo = xp[..., 0], xp[..., 1]
    c = cos[:, :, None, :]
    sn = sin[:, :, None, :]
    out = torch.stack([xe * c - xo * sn, xe * sn + xo * c], dim=-1)
    return out.reshape(b, s, h, d)


def t_attention(q, k, v):
    # f32 softmax attention on (B, S, H, D), matching ops/attention._xla_attention.
    d = q.shape[-1]
    logits = torch.einsum("bqhd,bkhd->bhqk", q, k).float() / np.sqrt(d)
    probs = torch.softmax(logits, dim=-1)
    return torch.einsum("bhqk,bkhd->bqhd", probs, v)


def t_modulate(x, shift, scale):
    return x.float() * (1.0 + scale) + shift


def _ln(x, h):
    return F.layer_norm(x, (h,), eps=1e-6)


class TDoubleBlock(tnn.Module):
    def __init__(self, h, heads, mlp_dim):
        super().__init__()
        self.h, self.heads = h, heads
        self.img_mod = TModulation(h, 2)
        self.txt_mod = TModulation(h, 2)
        self.img_attn = TSelfAttention(h, heads)
        self.txt_attn = TSelfAttention(h, heads)
        self.img_mlp = tnn.Sequential(
            tnn.Linear(h, mlp_dim), tnn.GELU(approximate="tanh"), tnn.Linear(mlp_dim, h)
        )
        self.txt_mlp = tnn.Sequential(
            tnn.Linear(h, mlp_dim), tnn.GELU(approximate="tanh"), tnn.Linear(mlp_dim, h)
        )

    def _qkv(self, attn, x):
        b, s, _ = x.shape
        qkv = attn.qkv(x).reshape(b, s, 3, self.heads, self.h // self.heads)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        return attn.norm.query_norm(q), attn.norm.key_norm(k), v

    def forward(self, img, txt, vec, cos, sin):
        h = self.h
        ims1, isc1, ig1, ims2, isc2, ig2 = self.img_mod(vec)
        tms1, tsc1, tg1, tms2, tsc2, tg2 = self.txt_mod(vec)

        iq, ik, iv = self._qkv(self.img_attn, t_modulate(_ln(img, h), ims1, isc1))
        tq, tk, tv = self._qkv(self.txt_attn, t_modulate(_ln(txt, h), tms1, tsc1))
        q = t_apply_rope(torch.cat([tq, iq], dim=1), cos, sin)
        k = t_apply_rope(torch.cat([tk, ik], dim=1), cos, sin)
        v = torch.cat([tv, iv], dim=1)
        attn = t_attention(q, k, v).reshape(q.shape[0], q.shape[1], -1)
        txt_len = txt.shape[1]
        txt_a, img_a = attn[:, :txt_len], attn[:, txt_len:]

        img = img + ig1 * self.img_attn.proj(img_a)
        txt = txt + tg1 * self.txt_attn.proj(txt_a)
        img = img + ig2 * self.img_mlp(t_modulate(_ln(img, h), ims2, isc2))
        txt = txt + tg2 * self.txt_mlp(t_modulate(_ln(txt, h), tms2, tsc2))
        return img, txt


class TSingleBlock(tnn.Module):
    def __init__(self, h, heads, mlp_dim):
        super().__init__()
        self.h, self.heads, self.mlp_dim = h, heads, mlp_dim
        self.modulation = TModulation(h, 1)
        self.linear1 = tnn.Linear(h, 3 * h + mlp_dim)
        self.linear2 = tnn.Linear(h + mlp_dim, h)
        self.norm = TQKNorm(h // heads)

    def forward(self, x, vec, cos, sin):
        h, heads = self.h, self.heads
        shift, scale, gate = self.modulation(vec)
        x_n = t_modulate(_ln(x, h), shift, scale)
        fused = self.linear1(x_n)
        qkv, mlp = fused[..., : 3 * h], fused[..., 3 * h :]
        b, s, _ = x.shape
        qkv = qkv.reshape(b, s, 3, heads, h // heads)
        q = self.norm.query_norm(qkv[:, :, 0])
        k = self.norm.key_norm(qkv[:, :, 1])
        v = qkv[:, :, 2]
        q, k = t_apply_rope(q, cos, sin), t_apply_rope(k, cos, sin)
        attn = t_attention(q, k, v).reshape(b, s, -1)
        out = self.linear2(torch.cat([attn, F.gelu(mlp, approximate="tanh")], dim=-1))
        return x + gate * out


class TFinalLayer(tnn.Module):
    def __init__(self, h, out_dim):
        super().__init__()
        self.adaLN_modulation = tnn.Sequential(tnn.SiLU(), tnn.Linear(h, 2 * h))
        self.linear = tnn.Linear(h, out_dim)


class TFlux(tnn.Module):
    def __init__(self, cfg: FluxConfig):
        super().__init__()
        self.cfg = cfg
        h = cfg.hidden_size
        mlp = int(h * cfg.mlp_ratio)
        self.img_in = tnn.Linear(cfg.in_channels, h)
        self.txt_in = tnn.Linear(cfg.context_in_dim, h)
        self.time_in = TMLPEmbedder(256, h)
        self.vector_in = TMLPEmbedder(cfg.vec_in_dim, h)
        if cfg.guidance_embed:
            self.guidance_in = TMLPEmbedder(256, h)
        self.double_blocks = tnn.ModuleList(
            [TDoubleBlock(h, cfg.num_heads, mlp) for _ in range(cfg.depth)]
        )
        self.single_blocks = tnn.ModuleList(
            [TSingleBlock(h, cfg.num_heads, mlp) for _ in range(cfg.depth_single_blocks)]
        )
        self.final_layer = TFinalLayer(h, cfg.in_channels)

    def forward(self, x, timesteps, context, y, guidance):
        cfg = self.cfg
        B, Hh, Ww, C = x.shape
        p = cfg.patch_size
        hp, wp = Hh // p, Ww // p

        # BFL: rearrange(img, "b c (h ph) (w pw) -> b (h w) (c ph pw)") — a
        # token's features run channel-first, then the patch's rows, columns.
        img = x.reshape(B, hp, p, wp, p, C).permute(0, 1, 3, 5, 2, 4)
        img = img.reshape(B, hp * wp, C * p * p)
        img = self.img_in(img)
        txt = self.txt_in(context)

        vec = self.time_in(t_timestep_embedding(timesteps, 256))
        if cfg.guidance_embed:
            vec = vec + self.guidance_in(t_timestep_embedding(guidance, 256))
        vec = vec + self.vector_in(y)

        txt_len = txt.shape[1]
        txt_ids = torch.zeros(B, txt_len, 3, dtype=torch.int64)
        hh = torch.arange(hp)[:, None].expand(hp, wp)
        ww = torch.arange(wp)[None, :].expand(hp, wp)
        grid = torch.stack([torch.zeros_like(hh), hh, ww], dim=-1).reshape(1, hp * wp, 3)
        ids = torch.cat([txt_ids, grid.expand(B, hp * wp, 3)], dim=1)
        cos, sin = t_rope_freqs(ids, cfg.axes_dim, cfg.theta)

        for blk in self.double_blocks:
            img, txt = blk(img, txt, vec, cos, sin)
        x_seq = torch.cat([txt, img], dim=1)
        for blk in self.single_blocks:
            x_seq = blk(x_seq, vec, cos, sin)
        img = x_seq[:, txt_len:]

        shift, scale = self.final_layer.adaLN_modulation(vec.float())[:, None, :].chunk(
            2, dim=-1
        )
        img = t_modulate(_ln(img, cfg.hidden_size), shift, scale)
        img = self.final_layer.linear(img)
        # "b (h w) (c ph pw) -> b c (h ph) (w pw)", then back to NHWC
        img = img.reshape(B, hp, wp, C, p, p).permute(0, 1, 4, 2, 5, 3)
        return img.reshape(B, Hh, Ww, C)


# ---------------------------------------------------------------------------------
# The golden comparison
# ---------------------------------------------------------------------------------


@pytest.fixture(scope="module")
def torch_flux():
    torch.manual_seed(0)
    return TFlux(CFG).eval()


def test_full_forward_golden_parity(torch_flux):
    sd = {k: v.detach() for k, v in torch_flux.state_dict().items()}
    params = convert_flux_checkpoint(sd, CFG)
    model = build_flux(CFG, params=params, sample_shape=(1, 8, 8, 4), txt_len=8)

    rng = np.random.default_rng(7)
    x = rng.normal(size=(2, 8, 8, 4)).astype(np.float32)
    t = np.array([0.9, 0.3], np.float32)
    ctx = rng.normal(size=(2, 8, CFG.context_in_dim)).astype(np.float32)
    y = rng.normal(size=(2, CFG.vec_in_dim)).astype(np.float32)
    g = np.array([3.5, 4.0], np.float32)

    with torch.no_grad():
        want = torch_flux(
            torch.from_numpy(x), torch.from_numpy(t), torch.from_numpy(ctx),
            torch.from_numpy(y), torch.from_numpy(g),
        ).numpy()
    got = np.asarray(
        model.apply(model.params, jnp.asarray(x), jnp.asarray(t), jnp.asarray(ctx),
                    y=jnp.asarray(y), guidance=jnp.asarray(g))
    )
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_double_block_golden_parity(torch_flux):
    # Block-level isolation: feed identical hidden states straight into block 0 of
    # both implementations, so a failure localizes to the double block itself.
    sd = {k: v.detach() for k, v in torch_flux.state_dict().items()}
    params = convert_flux_checkpoint(sd, CFG)
    model = build_flux(CFG, params=params, sample_shape=(1, 8, 8, 4), txt_len=8)

    rng = np.random.default_rng(11)
    B, S_img, S_txt, h = 2, 16, 8, CFG.hidden_size
    img = rng.normal(size=(B, S_img, h)).astype(np.float32)
    txt = rng.normal(size=(B, S_txt, h)).astype(np.float32)
    vec = rng.normal(size=(B, h)).astype(np.float32)
    ids = rng.integers(0, 5, size=(B, S_txt + S_img, 3))

    t_cos, t_sin = t_rope_freqs(torch.from_numpy(ids), CFG.axes_dim, CFG.theta)
    with torch.no_grad():
        w_img, w_txt = torch_flux.double_blocks[0](
            torch.from_numpy(img), torch.from_numpy(txt), torch.from_numpy(vec),
            t_cos, t_sin,
        )

    from comfyui_parallelanything_tpu.models.flux import FluxModel
    from comfyui_parallelanything_tpu.ops.rope import axis_rope_freqs

    cos, sin = axis_rope_freqs(jnp.asarray(ids), CFG.axes_dim, CFG.theta)
    module = FluxModel(CFG)
    carry = {
        "img": jnp.asarray(img), "txt": jnp.asarray(txt), "vec": jnp.asarray(vec),
        "rope_cos": cos, "rope_sin": sin,
    }
    out = module.apply(
        {"params": model.params}, carry, 0, method=FluxModel.double_step
    )
    np.testing.assert_allclose(np.asarray(out["img"]), w_img.numpy(), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(out["txt"]), w_txt.numpy(), rtol=2e-4, atol=2e-4)


def test_single_block_golden_parity(torch_flux):
    sd = {k: v.detach() for k, v in torch_flux.state_dict().items()}
    params = convert_flux_checkpoint(sd, CFG)
    model = build_flux(CFG, params=params, sample_shape=(1, 8, 8, 4), txt_len=8)

    rng = np.random.default_rng(13)
    B, S_txt, S_img, h = 2, 8, 16, CFG.hidden_size
    txt = rng.normal(size=(B, S_txt, h)).astype(np.float32)
    img = rng.normal(size=(B, S_img, h)).astype(np.float32)
    vec = rng.normal(size=(B, h)).astype(np.float32)
    ids = rng.integers(0, 5, size=(B, S_txt + S_img, 3))

    x_seq = np.concatenate([txt, img], axis=1)
    t_cos, t_sin = t_rope_freqs(torch.from_numpy(ids), CFG.axes_dim, CFG.theta)
    with torch.no_grad():
        want = torch_flux.single_blocks[1](
            torch.from_numpy(x_seq), torch.from_numpy(vec), t_cos, t_sin
        ).numpy()

    from comfyui_parallelanything_tpu.models.flux import FluxModel
    from comfyui_parallelanything_tpu.ops.rope import axis_rope_freqs

    cos, sin = axis_rope_freqs(jnp.asarray(ids), CFG.axes_dim, CFG.theta)
    module = FluxModel(CFG)
    carry = {
        "img": jnp.asarray(img), "txt": jnp.asarray(txt), "vec": jnp.asarray(vec),
        "rope_cos": cos, "rope_sin": sin,
    }
    out = module.apply(
        {"params": model.params}, carry, 1, method=FluxModel.single_step
    )
    got = np.concatenate([np.asarray(out["txt"]), np.asarray(out["img"])], axis=1)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
