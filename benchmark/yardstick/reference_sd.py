"""Plain reference of the served Stable Diffusion path (SD1.x and SDXL), from
the published descriptions: CLIP text towers (openai/CLIP, open_clip), the ldm
``UNetModel`` with ``SpatialTransformer`` (CompVis/stable-diffusion
``openaimodel.py`` / ``attention.py``, Stability-AI/generative-models for the
SDXL keys), the FreeU_V2 patch (Si et al. 2023, as ComfyUI's ``FreeU_V2``
node), k-diffusion's Karras schedule and DPM-Solver++(2M), classifier-free
guidance, and the kl-f8 decoder (``ldm/modules/diffusionmodules/model.py``).

Straightforward ``jax.numpy`` in float32 on the checkpoint's own tensors
(torch layout: NCHW activations, OIHW kernels, ``(out, in)`` matrices), every
contraction at ``Precision.HIGHEST`` (see ``_contract``). It imports nothing of the program and
reads only the file the benchmark wrote. ``precision`` selects the arithmetic:

- ``float32`` — the reference proper;
- ``bfloat16`` — contractions on bfloat16-rounded operands, float32
  accumulation, bfloat16 results: the precision the configurations state. A
  run computes it beside the float32 reference as the unit of its comparison:
  how far the stated precision itself lies from float32 on this request;
- ``int8`` — the control, one step below what is stated: every matrix product
  and convolution on int8 operands (weights rounded to symmetric int8 per
  output channel, activations to symmetric int8 per token for a matrix
  product and per sample for a convolution, scaled back after), attention's
  two products on bfloat16 operands. int8 values are bfloat16-exact, so the same
  rounding path computes them exactly.

Departures from the published code, all below bfloat16 resolution: GroupNorm
and LayerNorm statistics in float32 whatever the mode; the sigma table from a
float64 cumulative product; a convolution as one matrix product over its
laid-out window (``_conv``) and FreeU's Fourier mask as four coefficients
(``_lowest_frequencies``), the same sums written so that the chip's compiler
takes a third of the time.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
PRECISIONS = ("float32", "bfloat16", "int8")


# -- arithmetic policy ------------------------------------------------------


def load_weights(sd: dict) -> dict:
    """Checkpoint tensors → device arrays in the checkpoint's own type; each
    block widens what it uses to float32, so the reference never holds a
    float32 copy of the model and the run's memory peak stays the program's."""
    return {k: jnp.asarray(np.asarray(v)) for k, v in sd.items()}


def _sub(sd: dict, prefix: str) -> dict:
    return {k[len(prefix):]: v for k, v in sd.items() if k.startswith(prefix)}


@functools.lru_cache(maxsize=None)
def _jitted(fn, *static):
    """One compiled program per block kind and static setting; XLA compiles it
    once per distinct shape, so the whole model costs a dozen small compiles
    instead of one that takes minutes."""
    return jax.jit(functools.partial(fn, *static))


def _weight(p: str, w):
    """A matrix or kernel as the arithmetic sees it: float32, and under
    int8 rounded to symmetric int8 per output channel (axis 0)."""
    w = w.astype(F32)
    if p == "int8":
        w = _int8(w, tuple(range(1, w.ndim)))
    return w


def _bf16_backend() -> bool:
    """Whether contractions can take bfloat16 operands and accumulate in
    float32 natively (a TPU does; this CPU backend has no such dot)."""
    return NATIVE_BF16 if NATIVE_BF16 is not None else jax.default_backend() == "tpu"


NATIVE_BF16 = None  # tests may pin either path


def _pieces(a):
    """A float32 array as three bfloat16 arrays that sum to it (to 2**-24 of
    its size): what ``Precision.HIGHEST`` does on a TPU."""
    hi = a.astype(jnp.bfloat16)
    r = a - hi.astype(F32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(F32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _contract(precision: str, op, a, b):
    """``op(a, b)`` — a bilinear contraction — in the arithmetic of
    ``precision``. Every product is taken on bfloat16 operands with float32
    accumulation: natively where the backend has such a contraction, else on
    the operands widened back to float32 at ``Precision.HIGHEST`` (a product of
    two bfloat16 values is exact in float32, so the two are the same
    arithmetic). Below float32 that is the whole of it, and the result is
    rounded to bfloat16 as the program's modules hand it on. float32 is the
    six-term sum over bfloat16 pieces — hi·hi, hi·mid, mid·hi, hi·lo, lo·hi,
    mid·mid — which is how ``Precision.HIGHEST`` computes a float32 product on
    the MXU; written out because the chip's float32 convolutions at
    ``HIGHEST`` ran fifteen times slower (my chip run, PR 23). It agrees with
    the CPU's true float32 (benchmark/tests)."""
    native = _bf16_backend()

    def mul(x, y):
        if native:
            return op(x, y, None, F32)
        return op(x.astype(F32), y.astype(F32), lax.Precision.HIGHEST, None)

    if precision != "float32":
        y = mul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))
        return y.astype(jnp.bfloat16).astype(F32)
    (a0, a1, a2), (b0, b1, b2) = _pieces(a), _pieces(b)
    small = mul(a0, b2) + mul(a2, b0) + mul(a1, b1)
    return (small + (mul(a0, b1) + mul(a1, b0))) + mul(a0, b0)


def _ein(precision: str, spec: str, a, b):
    return _contract(
        precision,
        lambda x, y, prec, out: jnp.einsum(spec, x, y, precision=prec,
                                           preferred_element_type=out), a, b)


def _int8(x, axes):
    """Symmetric int8 with one scale per slice over ``axes``, scaled back."""
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s) * s


def _linear(p, x, w, b=None):
    if p == "int8":
        x = _int8(x, (-1,))
    y = _ein(p, "...i,oi->...o", x, _weight(p, w))
    return y if b is None else y + b.astype(F32)


IM2COL_BYTES = 1 << 31


def _conv(p, x, w, b, stride=1, pad=None):
    """A convolution as one matrix product over the kernel's window laid out
    along the channels (``k*k`` shifted views of the padded input), the same
    sums in another order. The chip's compiler takes seconds for every
    distinct convolution shape and a fraction of that for the matrix product
    (9 to 22 s against 1 to 1.6 s at 2 x 320 x 64 x 64, my compile for a
    described v5e, PR 23), and a fresh checkout compiles some eighty. Where
    the laid-out input would pass ``IM2COL_BYTES`` (the decoder's last levels)
    it stays a convolution."""
    k = w.shape[-1]
    pad = (k // 2) if pad is None else pad
    if p == "int8":
        x = _int8(x, (1, 2, 3))
    wf = _weight(p, w)
    n, c, hh, ww = x.shape
    oh, ow = (hh + 2 * pad - k) // stride + 1, (ww + 2 * pad - k) // stride + 1
    if 4 * n * c * k * k * oh * ow <= IM2COL_BYTES:
        xp = jnp.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
        cols = [xp[:, :, dy:dy + stride * (oh - 1) + 1:stride,
                   dx:dx + stride * (ow - 1) + 1:stride]
                for dy in range(k) for dx in range(k)]
        y = _ein(p, "nchw,oc->nohw", jnp.concatenate(cols, 1),
                 jnp.transpose(wf, (0, 2, 3, 1)).reshape(wf.shape[0], -1))
    else:
        y = _contract(p, lambda u, v, prec, out: lax.conv_general_dilated(
            u, v, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            precision=prec, preferred_element_type=out), x, wf)
    return y + b.astype(F32)[None, :, None, None]


def _group_norm(x, w, b, groups=32, eps=1e-5):
    n, c = x.shape[:2]
    g = x.reshape(n, groups, -1)
    mean = g.mean(-1, keepdims=True)
    var = ((g - mean) ** 2).mean(-1, keepdims=True)
    g = (g - mean) * lax.rsqrt(var + eps)
    shape = (1, c) + (1,) * (x.ndim - 2)
    return (g.reshape(x.shape) * w.astype(F32).reshape(shape)
            + b.astype(F32).reshape(shape))


def _layer_norm(x, w, b, eps=1e-5):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * w.astype(F32) + b.astype(F32)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _gelu(x):
    return 0.5 * x * (1.0 + lax.erf(x / math.sqrt(2.0)))


def _attention(p, q, k, v, heads, bias=None):
    """(B, S, C) streams → multi-head softmax(q k^T / sqrt(d)) v; the two
    products on bfloat16 operands under every mode below float32."""
    p = "float32" if p == "float32" else "bfloat16"
    b, s, c = q.shape
    d = c // heads
    q, k, v = (t.reshape(t.shape[0], t.shape[1], heads, d) for t in (q, k, v))
    logits = _ein(p, "bqhd,bkhd->bhqk", q, k) * (d ** -0.5)
    if bias is not None:
        logits = logits + bias
    probs = jax.nn.softmax(logits, axis=-1)
    return _ein(p, "bhqk,bkhd->bqhd", probs, v).reshape(b, s, c)


# -- text towers -------------------------------------------------------------


def _causal(s: int):
    return jnp.where(jnp.tril(jnp.ones((s, s), bool)), 0.0, -jnp.inf)[None, None]


def _clip_hf_layer(p, heads, act, w, x):
    h = _layer_norm(x, w["layer_norm1.weight"], w["layer_norm1.bias"])
    q, k, v = (_linear(p, h, w[f"self_attn.{m}_proj.weight"],
                       w[f"self_attn.{m}_proj.bias"]) for m in "qkv")
    a = _attention(p, q, k, v, heads, _causal(x.shape[1]))
    x = x + _linear(p, a, w["self_attn.out_proj.weight"], w["self_attn.out_proj.bias"])
    h = _layer_norm(x, w["layer_norm2.weight"], w["layer_norm2.bias"])
    h = _linear(p, h, w["mlp.fc1.weight"], w["mlp.fc1.bias"])
    h = h * jax.nn.sigmoid(1.702 * h) if act == "quick_gelu" else _gelu(h)
    return x + _linear(p, h, w["mlp.fc2.weight"], w["mlp.fc2.bias"])


def clip_hf_text(p, sd, tokens, c: dict):
    """HF ``CLIPTextModel`` (keys under ``text_model.``): returns the final
    layer-normed stream, the raw stream entering the last layer, and the
    layer-normed state at the first EOS."""
    t = "text_model."
    x = sd[t + "embeddings.token_embedding.weight"].astype(F32)[tokens]
    x = x + sd[t + "embeddings.position_embedding.weight"].astype(F32)[None, : tokens.shape[1]]
    layer = _jitted(_clip_hf_layer, p, c["num_attention_heads"], c["hidden_act"])
    penultimate = None
    n = c["num_hidden_layers"]
    for i in range(n):
        if i == n - 1:
            penultimate = x
        x = layer(_sub(sd, f"{t}encoder.layers.{i}."), x)
    last = _layer_norm(x, sd[t + "final_layer_norm.weight"],
                       sd[t + "final_layer_norm.bias"])
    eos = jnp.argmax(tokens == c["eos_token_id"], axis=-1)
    pooled = jnp.take_along_axis(last, eos[:, None, None], axis=1)[:, 0]
    return last, penultimate, pooled


def _open_clip_layer(p, heads, w, x):
    h = _layer_norm(x, w["ln_1.weight"], w["ln_1.bias"])
    qkv = _linear(p, h, w["attn.in_proj_weight"], w["attn.in_proj_bias"])
    q, k, v = jnp.split(qkv, 3, axis=-1)
    a = _attention(p, q, k, v, heads, _causal(x.shape[1]))
    x = x + _linear(p, a, w["attn.out_proj.weight"], w["attn.out_proj.bias"])
    h = _layer_norm(x, w["ln_2.weight"], w["ln_2.bias"])
    h = _gelu(_linear(p, h, w["mlp.c_fc.weight"], w["mlp.c_fc.bias"]))
    return x + _linear(p, h, w["mlp.c_proj.weight"], w["mlp.c_proj.bias"])


def open_clip_text(p, sd, tokens, c: dict):
    """OpenCLIP text tower (fused ``in_proj``, ``resblocks``): final stream,
    penultimate stream, pooled = ln_final state at the first EOS @
    text_projection."""
    x = sd["token_embedding.weight"].astype(F32)[tokens]
    x = x + sd["positional_embedding"].astype(F32)[None, : tokens.shape[1]]
    layer = _jitted(_open_clip_layer, p, c["num_attention_heads"])
    penultimate = None
    n = c["num_hidden_layers"]
    for i in range(n):
        if i == n - 1:
            penultimate = x
        x = layer(_sub(sd, f"transformer.resblocks.{i}."), x)
    last = _layer_norm(x, sd["ln_final.weight"], sd["ln_final.bias"])
    eos = jnp.argmax(tokens == c["eos_token_id"], axis=-1)
    pooled = jnp.take_along_axis(last, eos[:, None, None], axis=1)[:, 0]
    pooled = _ein(p, "bi,io->bo", pooled, sd["text_projection"].astype(F32))
    return last, penultimate, pooled


def timestep_embedding(t, dim: int, max_period: float = 10000.0):
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) * jnp.arange(half, dtype=F32) / half)
    args = t.astype(F32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


# -- the denoiser -------------------------------------------------------------


def _unet_embed(p, mc, w, t, y):
    emb = _linear(p, timestep_embedding(t, mc), w["time_embed.0.weight"], w["time_embed.0.bias"])
    emb = _linear(p, _silu(emb), w["time_embed.2.weight"], w["time_embed.2.bias"])
    if y is not None:
        e = _linear(p, y, w["label_emb.0.0.weight"], w["label_emb.0.0.bias"])
        emb = emb + _linear(p, _silu(e), w["label_emb.0.2.weight"], w["label_emb.0.2.bias"])
    return emb


def _unet_res(p, w, h, emb):
    r = _group_norm(h, w["in_layers.0.weight"], w["in_layers.0.bias"])
    r = _conv(p, _silu(r), w["in_layers.2.weight"], w["in_layers.2.bias"])
    e = _linear(p, _silu(emb), w["emb_layers.1.weight"], w["emb_layers.1.bias"])
    r = r + e[:, :, None, None]
    r = _group_norm(r, w["out_layers.0.weight"], w["out_layers.0.bias"])
    r = _conv(p, _silu(r), w["out_layers.3.weight"], w["out_layers.3.bias"])
    if "skip_connection.weight" in w:
        h = _conv(p, h, w["skip_connection.weight"], w["skip_connection.bias"])
    return h + r


def _unet_transformer(p, heads, linear, depth, w, h, context):
    n, c, hh, ww = h.shape
    r = _group_norm(h, w["norm.weight"], w["norm.bias"], eps=1e-6)
    if linear:
        r = r.reshape(n, c, hh * ww).transpose(0, 2, 1)
        r = _linear(p, r, w["proj_in.weight"], w["proj_in.bias"])
    else:
        r = _conv(p, r, w["proj_in.weight"], w["proj_in.bias"])
        r = r.reshape(n, c, hh * ww).transpose(0, 2, 1)
    for d in range(depth):
        b = f"transformer_blocks.{d}"
        for i, a, kv in ((1, "attn1", None), (2, "attn2", context)):
            z = _layer_norm(r, w[f"{b}.norm{i}.weight"], w[f"{b}.norm{i}.bias"])
            src = z if kv is None else kv
            o = _attention(
                p, _linear(p, z, w[f"{b}.{a}.to_q.weight"]),
                _linear(p, src, w[f"{b}.{a}.to_k.weight"]),
                _linear(p, src, w[f"{b}.{a}.to_v.weight"]), heads)
            r = r + _linear(p, o, w[f"{b}.{a}.to_out.0.weight"], w[f"{b}.{a}.to_out.0.bias"])
        z = _layer_norm(r, w[f"{b}.norm3.weight"], w[f"{b}.norm3.bias"])
        z = _linear(p, z, w[f"{b}.ff.net.0.proj.weight"], w[f"{b}.ff.net.0.proj.bias"])
        val, gate = jnp.split(z, 2, axis=-1)
        r = r + _linear(p, val * _gelu(gate), w[f"{b}.ff.net.2.weight"], w[f"{b}.ff.net.2.bias"])
    if linear:
        r = _linear(p, r, w["proj_out.weight"], w["proj_out.bias"])
        r = r.transpose(0, 2, 1).reshape(n, c, hh, ww)
    else:
        r = r.transpose(0, 2, 1).reshape(n, c, hh, ww)
        r = _conv(p, r, w["proj_out.weight"], w["proj_out.bias"])
    return h + r


def _plain_conv(p, stride, upsample, w, h):
    if upsample:
        h = jnp.repeat(jnp.repeat(h, 2, axis=2), 2, axis=3)
    return _conv(p, h, w["weight"], w["bias"], stride=stride, pad=1)


def _unet_out(p, w, h):
    h = _silu(_group_norm(h, w["out.0.weight"], w["out.0.bias"]))
    return _conv(p, h, w["out.2.weight"], w["out.2.bias"])


def _freeu(bb, ss, h, skip):
    """FreeU_V2 at one junction: the backbone's first half-channels scaled by
    its own normalised channel mean, the skip's lowest frequencies by ``ss``."""
    mean = h.mean(1, keepdims=True)
    lo = mean.min(axis=(1, 2, 3), keepdims=True)
    hi = mean.max(axis=(1, 2, 3), keepdims=True)
    mean = (mean - lo) / jnp.maximum(hi - lo, 1e-8)
    half = h.shape[1] // 2
    h = jnp.concatenate([h[:, :half] * ((bb - 1.0) * mean + 1.0), h[:, half:]], 1)
    return h, skip + (ss - 1.0) * _lowest_frequencies(skip)


def _lowest_frequencies(x):
    """The part of ``x`` (..., H, W) at the 2 x 2 lowest frequencies, (-1, 0)
    on each axis: what the node's centred 2 x 2 Fourier mask scales. Four
    Fourier coefficients by their defining sums and the real part of their
    inverse, instead of a whole FFT and its inverse: the same numbers to
    float32 rounding, and the chip's compiler takes 8 to 20 s for each FFT
    shape (my compile for a described v5e, PR 23)."""
    hh, ww = x.shape[-2:]
    k = np.asarray([-1.0, 0.0])[:, None]
    ey = np.exp(-2j * np.pi * k * np.arange(hh) / hh).astype(np.complex64)  # (2, H)
    ex = np.exp(-2j * np.pi * k * np.arange(ww) / ww).astype(np.complex64)  # (2, W)
    a = (x[..., None, :, :] * ey[:, :, None]).sum(-2)              # (..., 2, W)
    f = (a[..., :, None, :] * ex).sum(-1)                          # (..., 2, 2)
    b = (f[..., :, :, None] * np.conj(ex)).sum(-2)                # (..., 2, W)
    low = (b[..., :, None, :] * np.conj(ey)[:, :, None]).sum(-3)  # (..., H, W)
    return low.real.astype(F32) / (hh * ww)


def unet(p, sd, u: dict, x, t, context, y=None, freeu=None):
    """ldm ``UNetModel.forward`` on NCHW ``x``, block by block; ``freeu`` =
    (b1, b2, s1, s2) applies FreeU_V2 at the output blocks whose backbone is
    4x / 2x the base width (threshold 1, as the node sets it)."""
    from .layout import unet_attention_levels, unet_depths, unet_heads

    mc = u["model_channels"]
    depths, attn = unet_depths(u), unet_attention_levels(u)
    linear = bool(u.get("use_linear_in_transformer"))
    res = _jitted(_unet_res, p)

    def transformer(key, h, depth):
        fn = _jitted(_unet_transformer, p, unet_heads(u, h.shape[1]), linear, depth)
        return fn(_sub(sd, key + "."), h, context)

    emb = _jitted(_unet_embed, p, mc)(
        {k: v for k, v in sd.items() if k.startswith(("time_embed.", "label_emb."))},
        t, y if u.get("adm_in_channels") else None)
    levels = range(len(u["channel_mult"]))
    h = _jitted(_plain_conv, p, 1, False)(_sub(sd, "input_blocks.0.0."), x)
    skips, idx = [h], 1
    for lvl in levels:
        for _ in range(u["num_res_blocks"]):
            h = res(_sub(sd, f"input_blocks.{idx}.0."), h, emb)
            if lvl in attn and depths[lvl] > 0:
                h = transformer(f"input_blocks.{idx}.1", h, depths[lvl])
            skips.append(h)
            idx += 1
        if lvl != levels[-1]:
            h = _jitted(_plain_conv, p, 2, False)(_sub(sd, f"input_blocks.{idx}.0.op."), h)
            skips.append(h)
            idx += 1
    h = res(_sub(sd, "middle_block.0."), h, emb)
    h = transformer("middle_block.1", h, depths[-1] or 1)
    h = res(_sub(sd, "middle_block.2."), h, emb)
    idx = 0
    for lvl in reversed(levels):
        for i in range(u["num_res_blocks"] + 1):
            skip = skips.pop()
            if freeu is not None:
                b1, b2, s1, s2 = freeu
                stage = {mc * 4: (b1, s1), mc * 2: (b2, s2)}.get(h.shape[1])
                if stage is not None:
                    h, skip = _jitted(_freeu, *stage)(h, skip)
            h = res(_sub(sd, f"output_blocks.{idx}.0."), jnp.concatenate([h, skip], 1), emb)
            sub = 1
            if lvl in attn and depths[lvl] > 0:
                h = transformer(f"output_blocks.{idx}.1", h, depths[lvl])
                sub = 2
            if lvl and i == u["num_res_blocks"]:
                h = _jitted(_plain_conv, p, 1, True)(
                    _sub(sd, f"output_blocks.{idx}.{sub}.conv."), h)
            idx += 1
    return _jitted(_unet_out, p)({k: sd[k] for k in (
        "out.0.weight", "out.0.bias", "out.2.weight", "out.2.bias")}, h)


# -- the decoder --------------------------------------------------------------


def _vae_res(p, w, h):
    r = _group_norm(h, w["norm1.weight"], w["norm1.bias"], eps=1e-6)
    r = _conv(p, _silu(r), w["conv1.weight"], w["conv1.bias"])
    r = _group_norm(r, w["norm2.weight"], w["norm2.bias"], eps=1e-6)
    r = _conv(p, _silu(r), w["conv2.weight"], w["conv2.bias"])
    if "nin_shortcut.weight" in w:
        h = _conv(p, h, w["nin_shortcut.weight"], w["nin_shortcut.bias"])
    return h + r


def _vae_attn(p, w, h):
    n, c, hh, ww = h.shape
    r = _group_norm(h, w["norm.weight"], w["norm.bias"], eps=1e-6)
    q, k, v = (_conv(p, r, w[f"{m}.weight"], w[f"{m}.bias"])
               .reshape(n, c, hh * ww).transpose(0, 2, 1) for m in "qkv")
    o = _attention(p, q, k, v, 1).transpose(0, 2, 1).reshape(n, c, hh, ww)
    return h + _conv(p, o, w["proj_out.weight"], w["proj_out.bias"])


def _vae_in(p, scale, w, z):
    h = _conv(p, z / scale, w["post_quant_conv.weight"], w["post_quant_conv.bias"])
    return _conv(p, h, w["decoder.conv_in.weight"], w["decoder.conv_in.bias"])


def _vae_out(p, w, h):
    h = _silu(_group_norm(h, w["decoder.norm_out.weight"],
                          w["decoder.norm_out.bias"], eps=1e-6))
    return _conv(p, h, w["decoder.conv_out.weight"], w["decoder.conv_out.bias"])


def vae_decode(p, sd, v: dict, z):
    """Scaled latent (NCHW) → decoder output in [-1, 1] (NCHW), block by block."""
    res = _jitted(_vae_res, p)
    h = _jitted(_vae_in, p, float(v["scale_factor"]))(
        {k: sd[k] for k in sd if k.startswith(("post_quant_conv.", "decoder.conv_in."))}, z)
    h = res(_sub(sd, "decoder.mid.block_1."), h)
    h = _jitted(_vae_attn, p)(_sub(sd, "decoder.mid.attn_1."), h)
    h = res(_sub(sd, "decoder.mid.block_2."), h)
    for lvl in reversed(range(len(v["ch_mult"]))):
        for i in range(v["num_res_blocks"] + 1):
            h = res(_sub(sd, f"decoder.up.{lvl}.block.{i}."), h)
        if lvl != 0:
            h = _jitted(_plain_conv, p, 1, True)(
                _sub(sd, f"decoder.up.{lvl}.upsample.conv."), h)
    return _jitted(_vae_out, p)(
        {k: sd[k] for k in sd if k.startswith(("decoder.norm_out.", "decoder.conv_out."))}, h)


# -- schedule and sampler -----------------------------------------------------


def sigma_table(s: dict) -> np.ndarray:
    """ldm ``scaled_linear`` betas → per-timestep sigmas, ascending."""
    betas = np.linspace(s["linear_start"] ** 0.5, s["linear_end"] ** 0.5,
                        s["timesteps"], dtype=np.float64) ** 2
    acp = np.cumprod(1.0 - betas)
    return np.sqrt((1.0 - acp) / acp)


def karras_sigmas(n: int, sigma_min: float, sigma_max: float, rho: float = 7.0):
    ramp = np.linspace(0.0, 1.0, n)
    lo, hi = sigma_min ** (1 / rho), sigma_max ** (1 / rho)
    return np.append((hi + ramp * (lo - hi)) ** rho, 0.0)


def sigma_to_timestep(table: np.ndarray, sigma: float) -> float:
    """k-diffusion ``DiscreteSchedule.sigma_to_t``: interpolate in log sigma."""
    return float(np.interp(np.log(sigma), np.log(table),
                           np.arange(len(table), dtype=np.float64)))


def sample_dpmpp_2m(denoise, x, sigmas):
    """k-diffusion ``sample_dpmpp_2m``."""
    old = None
    for i in range(len(sigmas) - 1):
        s, s_next = float(sigmas[i]), float(sigmas[i + 1])
        x0 = denoise(x, s)
        if s_next == 0.0:
            return x0
        t, t_next = -math.log(s), -math.log(s_next)
        h = t_next - t
        if old is None:
            d = x0
        else:
            r = (t - (-math.log(float(sigmas[i - 1])))) / h
            d = (1 + 1 / (2 * r)) * x0 - (1 / (2 * r)) * old
        x = (s_next / s) * x - math.expm1(-h) * d
        old = x0
    return x


SAMPLERS = {"dpmpp_2m": sample_dpmpp_2m}


# -- the whole served path ------------------------------------------------------


def describe(graph: dict) -> dict:
    """What a stock txt2img graph asks for, read off the graph as sent (the
    reference follows this, not the template): one KSampler, its latent, its
    two text prompts, an optional FreeU_V2 between loader and sampler."""
    ks = [n for n in graph.values() if n["class_type"] == "KSampler"]
    if len(ks) != 1:
        raise ValueError("the reference reads graphs with exactly one KSampler")
    k = ks[0]["inputs"]

    def node(ref):
        return graph[ref[0]]

    latent = node(k["latent_image"])
    if latent["class_type"] != "EmptyLatentImage":
        raise ValueError("the reference reads txt2img graphs only")
    model, freeu = node(k["model"]), None
    while model["class_type"] != "CheckpointLoaderSimple":
        if model["class_type"] == "FreeU_V2":
            i = model["inputs"]
            freeu = (i["b1"], i["b2"], i["s1"], i["s2"])
        else:
            raise ValueError(f"the reference does not know {model['class_type']}")
        model = node(model["inputs"]["model"])
    ks_id = next(i for i, n in graph.items() if n is ks[0])
    dec = [n for n in graph.values() if n["class_type"].startswith("VAEDecode")
           and n["inputs"]["samples"][0] == ks_id]
    if [n["class_type"] for n in dec] != ["VAEDecode"]:
        raise ValueError("the reference reads graphs with one untiled VAEDecode")
    out = {"seed": k["seed"], "steps": k["steps"], "cfg": k["cfg"],
           "sampler_name": k["sampler_name"], "scheduler": k["scheduler"],
           "positive": node(k["positive"])["inputs"]["text"],
           "negative": node(k["negative"])["inputs"]["text"],
           "freeu": freeu, **latent["inputs"]}
    if k.get("denoise", 1.0) != 1.0:
        raise ValueError("the reference reads txt2img graphs only")
    return out


class Reference:
    """The served path of one configuration in one arithmetic. Weights go to
    the device once per part and are dropped with the object."""

    def __init__(self, config: dict, checkpoint: str, tokenizer, precision: str):
        from . import safetensors_io

        if precision not in PRECISIONS:
            raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
        self.c, self.p, self.tok = config, precision, tokenizer
        self.path = checkpoint
        self._read = functools.partial(safetensors_io.read, checkpoint)
        self.table = sigma_table(config["schedule"])

    def _part(self, prefix: str) -> dict:
        return load_weights(self._read(prefix))

    def _eos(self) -> int:
        return self.c["text"]["vocab_size"] - 1

    def encode(self, texts: list[str]):
        """→ (context (N, 77, D), y (N, adm) or None)."""
        c, p = self.c, self.p
        t = dict(c["text"], eos_token_id=self._eos())
        n_ctx = t["max_position_embeddings"]
        ids = jnp.asarray(np.stack([self.tok.ids(s, n_ctx) for s in texts]))
        prefix = next(q["prefix"] for q in c["checkpoint"]["parts"]
                      if q["sizes"] == "text")
        sd = self._part(prefix)
        last, pen, _ = clip_hf_text(p, sd, ids, t)
        del sd
        if "text_g" not in c:
            return (last if t["layer"] == "last" else pen), None
        g = dict(c["text_g"], eos_token_id=self._eos())
        ids_g = jnp.asarray(np.stack(
            [self.tok.ids(s, g["max_position_embeddings"], pad_id=0) for s in texts]))
        prefix = next(q["prefix"] for q in c["checkpoint"]["parts"]
                      if q["sizes"] == "text_g")
        sd = self._part(prefix)
        _, pen_g, pooled = open_clip_text(p, sd, ids_g, g)
        del sd
        a = c["adm"]
        sizes = [a["height"], a["width"], a["crop_y"], a["crop_x"],
                 a["target_height"], a["target_width"]]
        embs = [timestep_embedding(jnp.full((len(texts),), float(s)), a["embed_dim"])
                for s in sizes]
        return (jnp.concatenate([pen, pen_g], -1),
                jnp.concatenate([pooled] + embs, -1))

    def images(self, req: dict, rows: list[int]) -> np.ndarray:
        """Float images in [0, 1], (len(rows), H, W, 3), for the batch rows
        ``rows`` of one request: ``req`` has seed, positive, negative, width,
        height, batch_size, steps, cfg, sampler_name, scheduler, freeu (or
        None)."""
        c, p = self.c, self.p
        if req["scheduler"] != "karras" or req["sampler_name"] not in SAMPLERS:
            raise NotImplementedError(
                f"reference has no {req['sampler_name']}/{req['scheduler']}")
        if c["schedule"]["parameterization"] != "eps":
            raise NotImplementedError("reference covers eps-prediction only")
        context, y = self.encode([req["positive"], req["negative"]])
        h8, w8 = req["height"] // 8, req["width"] // 8
        # The served path draws the whole batch's noise as one NHWC array
        # from jax.random.key(seed): draw it likewise, keep the sampled rows.
        noise = jax.random.normal(jax.random.key(int(req["seed"]) % 2 ** 63),
                                  (req["batch_size"], h8, w8, c["unet"]["in_channels"]), F32)
        noise = jnp.transpose(noise[jnp.asarray(rows)], (0, 3, 1, 2))
        sd = self._part(next(q["prefix"] for q in c["checkpoint"]["parts"]
                             if q["sizes"] == "unet"))
        freeu = tuple(req["freeu"]) if req.get("freeu") else None
        scale = float(req["cfg"])

        def denoise(x, sigma):
            t = jnp.full((2,), sigma_to_timestep(self.table, sigma), F32)
            x_in = x / math.sqrt(sigma ** 2 + 1.0)
            eps = unet(p, sd, c["unet"], jnp.concatenate([x_in, x_in]), t, context,
                       y=y, freeu=freeu)
            return x - sigma * (eps[1:] + scale * (eps[:1] - eps[1:]))

        sigmas = karras_sigmas(req["steps"], float(self.table[0]), float(self.table[-1]))
        # One row at a time: a row does not see the others, every row runs
        # the programs the first compiled, and the reference's memory peak
        # stays under the program's however many rows a run compares.
        latents = [SAMPLERS[req["sampler_name"]](
            denoise, noise[k:k + 1] * float(sigmas[0]), sigmas).block_until_ready()
            for k in range(len(rows))]
        del sd
        sd = self._part(next(q["prefix"] for q in c["checkpoint"]["parts"]
                             if q["sizes"] == "vae"))
        imgs = [jnp.clip(vae_decode(p, sd, c["vae"], z) * 0.5 + 0.5, 0.0, 1.0)
                for z in latents]
        return np.asarray(jnp.transpose(jnp.concatenate(imgs), (0, 2, 3, 1)), np.float32)
