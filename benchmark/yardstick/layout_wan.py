"""The Wan2.2-T2V-A14B file layouts, written out from the published
descriptions: ``wan/modules/model.py`` of github.com/Wan-Video/Wan2.2
(``WanModel`` as ``high_noise_model`` / ``low_noise_model`` hold it, bare
keys, one file an expert), ``wan/modules/vae2_1.py`` (``WanVAE_``: the Wan2.1
causal 3-D autoencoder, ``Wan2.1_VAE.pth``'s keys), ``google/umt5-xxl``'s
encoder as ``HF T5EncoderModel`` keys (``layout_mmdit.t5_layout`` with
``per_layer_bias``, imported) and the rank-64 LoRA files of
``lightx2v/Wan2.2-Lightning`` in ComfyUI's key spelling (``assumed`` in the
configuration). Each function returns ``[(key, shape, kind)]`` like
``layout.py``'s; ``synth.write_checkpoint`` finds them through the
configuration's ``checkpoint.layouts``. The sizes are the published
``config.json`` keys (``dim``, ``ffn_dim``, ``num_heads``, ``num_layers``, …),
so a depth cut is one number in the configuration. Nothing here imports the
program.

Counts at the published sizes (``benchmark/tests/test_wan.py`` pins them): a
block 351,394,304 parameters; what an expert holds besides its blocks
232,719,424; an expert whole (40 blocks) 14,288,491,584, at the cut (5)
1,989,690,944; the tower 5,680,910,336."""

from __future__ import annotations

from .layout import _lin
from .layout_mmdit import t5_layout

__all__ = ["wan_layout", "umt5_layout", "wan_vae_layout", "wan_lora_layout",
           "LORA_TARGETS", "head_dim", "vae_dims"]

# The ten linears of a block that carry a LoRA delta, with (in, out) by name.
LORA_TARGETS = ("self_attn.q", "self_attn.k", "self_attn.v", "self_attn.o",
                "cross_attn.q", "cross_attn.k", "cross_attn.v", "cross_attn.o",
                "ffn.0", "ffn.2")


def head_dim(m: dict) -> int:
    return m["dim"] // m["num_heads"]


def wan_layout(m: dict) -> list[tuple]:
    """``WanModel`` (t2v): the patch embedding as a ``Conv3d`` whose kernel is
    its stride; the text embedder (two linears around a tanh-GELU), the time
    embedder (two around a SiLU) and the projection of the time vector to six
    modulation vectors; ``num_layers`` ``WanAttentionBlock``s — self- and
    cross-attention each with biased q / k / v / o and RMS-norm scales over
    the FULL width for q and k, an affine LayerNorm before the
    cross-attention (``cross_attn_norm``), a two-layer feed-forward, and a
    learned (1, 6, dim) modulation table drawn like the published
    initialiser, N(0, 1/dim); the head with its (1, 2, dim) table."""
    out: list[tuple] = []
    d, ff = m["dim"], m["ffn_dim"]
    pt, ph, pw = m["patch_size"]
    fan = m["in_dim"] * pt * ph * pw
    out.append(("patch_embedding.weight", (d, m["in_dim"], pt, ph, pw), f"w:{fan}"))
    out.append(("patch_embedding.bias", (d,), "bias"))
    _lin(out, "text_embedding.0", m["text_dim"], d)
    _lin(out, "text_embedding.2", d, d)
    _lin(out, "time_embedding.0", m["freq_dim"], d)
    _lin(out, "time_embedding.2", d, d)
    _lin(out, "time_projection.1", d, 6 * d)
    for i in range(m["num_layers"]):
        b = f"blocks.{i}"
        for attn in ("self_attn", "cross_attn"):
            for n in "qkvo":
                _lin(out, f"{b}.{attn}.{n}", d, d)
            out.append((f"{b}.{attn}.norm_q.weight", (d,), "norm"))
            out.append((f"{b}.{attn}.norm_k.weight", (d,), "norm"))
        out.append((f"{b}.norm3.weight", (d,), "norm"))
        out.append((f"{b}.norm3.bias", (d,), "bias"))
        _lin(out, f"{b}.ffn.0", d, ff)
        _lin(out, f"{b}.ffn.2", ff, d)
        out.append((f"{b}.modulation", (1, 6, d), f"w:{d}"))
    _lin(out, "head.head", d, m["out_dim"] * pt * ph * pw)
    out.append(("head.modulation", (1, 2, d), f"w:{d}"))
    return out


def umt5_layout(t: dict) -> list[tuple]:
    """``google/umt5-xxl``'s encoder: T5 v1.1's keys with a relative-position
    table in EVERY block."""
    return t5_layout(dict(t, per_layer_bias=True))


def wan_lora_layout(l: dict) -> list[tuple]:
    """A rank-``rank`` LoRA over the ten linears of every block, in the key
    spelling ComfyUI loads (``diffusion_model.blocks.N.<linear>.lora_down
    .weight`` (rank, in), ``.lora_up.weight`` (out, rank), ``.alpha`` a
    scalar). ``down`` is drawn N(0, 1/in), ``up`` at unit variance and
    ``alpha`` near 1, so that ``(alpha / rank) · up @ down`` is an eighth of
    the base kernel's own spread: a bake that is left out, or scaled wrongly,
    moves every frame far past the comparison's limit."""
    out: list[tuple] = []
    d, ff, r = l["dim"], l["ffn_dim"], l["rank"]
    for i in range(l["num_layers"]):
        for name in LORA_TARGETS:
            i_dim = ff if name == "ffn.2" else d
            o_dim = ff if name == "ffn.0" else d
            b = f"diffusion_model.blocks.{i}.{name}"
            out.append((f"{b}.lora_down.weight", (r, i_dim), f"w:{i_dim}"))
            out.append((f"{b}.lora_up.weight", (o_dim, r), "w:1"))
            out.append((f"{b}.alpha", (), "norm"))
    return out


def vae_dims(v: dict) -> list[int]:
    """``dim · [dim_mult[-1], *reversed(dim_mult)]``: the decoder's widths by
    stage, the first repeated for the middle (the encoder's are its
    reverse)."""
    mult = list(v["dim_mult"])
    return [v["dim"] * u for u in [mult[-1]] + mult[::-1]]


def _conv3d(out, key, i, o, k):
    kt, kh, kw = k
    out.append((f"{key}.weight", (o, i, kt, kh, kw), f"w:{i * kt * kh * kw}"))
    out.append((f"{key}.bias", (o,), "bias"))


def _conv2d(out, key, i, o, k):
    out.append((f"{key}.weight", (o, i, k, k), f"w:{i * k * k}"))
    out.append((f"{key}.bias", (o,), "bias"))


def _res(out, key, i, o):
    """``ResidualBlock``: RMS norm, SiLU, causal 3x3x3, RMS norm, SiLU,
    (dropout,) causal 3x3x3; a 1x1x1 shortcut where the widths differ."""
    out.append((f"{key}.residual.0.gamma", (i, 1, 1, 1), "norm"))
    _conv3d(out, f"{key}.residual.2", i, o, (3, 3, 3))
    out.append((f"{key}.residual.3.gamma", (o, 1, 1, 1), "norm"))
    _conv3d(out, f"{key}.residual.6", o, o, (3, 3, 3))
    if i != o:
        _conv3d(out, f"{key}.shortcut", i, o, (1, 1, 1))


def _middle(out, key, c):
    _res(out, f"{key}.0", c, c)
    out.append((f"{key}.1.norm.gamma", (c, 1, 1), "norm"))
    _conv2d(out, f"{key}.1.to_qkv", c, 3 * c, 1)
    _conv2d(out, f"{key}.1.proj", c, c, 1)
    _res(out, f"{key}.2", c, c)


def wan_vae_layout(v: dict) -> list[tuple]:
    """``WanVAE_``: ``Encoder3d`` (``conv1``; per stage ``num_res_blocks``
    residual blocks then, below the last, a ``Resample`` — a stride-2 Conv2d
    behind a zero pad, and a stride-2 causal (3, 1, 1) convolution where the
    stage halves time; the middle; the head), the 1x1x1 ``conv1`` / ``conv2``
    around the latent, ``Decoder3d`` (``conv1``; the middle; per stage
    ``num_res_blocks + 1`` residual blocks — the stages after an up-sampler
    start from HALF the width before it — then a ``Resample``: nearest x2 and
    a Conv2d that halves the width, with a causal (3, 1, 1) ``time_conv`` to
    TWICE the width where the stage doubles time; the head)."""
    out: list[tuple] = []
    z, n = v["z_dim"], len(v["dim_mult"])
    down_t = list(v["temperal_downsample"])
    enc = [v["dim"] * u for u in [1] + list(v["dim_mult"])]
    _conv3d(out, "encoder.conv1", 3, enc[0], (3, 3, 3))
    seq = 0
    for lvl, (i, o) in enumerate(zip(enc[:-1], enc[1:])):
        for _ in range(v["num_res_blocks"]):
            _res(out, f"encoder.downsamples.{seq}", i, o)
            i = o
            seq += 1
        if lvl != n - 1:
            _conv2d(out, f"encoder.downsamples.{seq}.resample.1", o, o, 3)
            if down_t[lvl]:
                _conv3d(out, f"encoder.downsamples.{seq}.time_conv", o, o, (3, 1, 1))
            seq += 1
    _middle(out, "encoder.middle", enc[-1])
    out.append(("encoder.head.0.gamma", (enc[-1], 1, 1, 1), "norm"))
    _conv3d(out, "encoder.head.2", enc[-1], 2 * z, (3, 3, 3))
    _conv3d(out, "conv1", 2 * z, 2 * z, (1, 1, 1))
    _conv3d(out, "conv2", z, z, (1, 1, 1))
    dec = vae_dims(v)
    _conv3d(out, "decoder.conv1", z, dec[0], (3, 3, 3))
    _middle(out, "decoder.middle", dec[0])
    up_t = down_t[::-1]
    seq = 0
    for lvl, (i, o) in enumerate(zip(dec[:-1], dec[1:])):
        if lvl:
            i //= 2  # the up-sampler before this stage halved the width
        for _ in range(v["num_res_blocks"] + 1):
            _res(out, f"decoder.upsamples.{seq}", i, o)
            i = o
            seq += 1
        if lvl != n - 1:
            _conv2d(out, f"decoder.upsamples.{seq}.resample.1", o, o // 2, 3)
            if up_t[lvl]:
                _conv3d(out, f"decoder.upsamples.{seq}.time_conv", o, 2 * o, (3, 1, 1))
            seq += 1
    out.append(("decoder.head.0.gamma", (dec[-1], 1, 1, 1), "norm"))
    _conv3d(out, "decoder.head.2", dec[-1], 3, (3, 3, 3))
    return out
