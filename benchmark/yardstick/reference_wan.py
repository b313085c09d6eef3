"""Plain reference of the served Wan2.2-T2V-A14B path, from the published
descriptions: ``wan/modules/model.py`` of github.com/Wan-Video/Wan2.2
(``WanModel``: the latent through a ``Conv3d`` whose kernel is its stride
(1, 2, 2), tokens in (frame, row, column) order; the time vector
``e = Linear(SiLU(Linear(cat[cos, sin](t · 10000^(−i/128)))))`` at t = 1000 σ
and its projection ``e0`` to six modulation vectors, float32; the UMT5 states
of the valid tokens, zeros after them up to ``text_len`` rows, through a
linear, a tanh-GELU and a linear; ``num_layers`` blocks — ``(s1, c1, g1, s2,
c2, g2) = modulation + e0``; self-attention on ``LN(x)(1 + c1) + s1`` with q
and k RMS-normed over the FULL width before the split into heads and turned
by a rotary of 64 complex pairs a head, 22 / 21 / 21 of them by frame / row /
column index, gated by ``g1``; cross-attention of ``LN_affine(x)`` on the text
with the same norms, no rotary, no gate, no mask; a tanh-GELU feed-forward on
``LN(x)(1 + c2) + s2`` gated by ``g2``; every linear biased — and the head:
``(s, c) = head.modulation + e`` with the UNPROJECTED ``e``, a linear to 64,
un-patchified with the channel fastest inside a patch vector),
``wan/modules/t5.py`` (``google/umt5-xxl``'s encoder with a relative-position
table in every block and padded keys masked: ``reference_t5.encode`` with
``per_layer_bias``, imported), ``wan/modules/vae2_1.py`` (the Wan2.1 causal
3-D decoder walked one latent frame at a time, every causal convolution
carrying the last two frames of its own input; the temporal up-sampler's
first-frame rule: the first frame passes as it is, ``time_conv`` runs over the
frames after it with zeros as their history, each of them becomes two),
``wan/configs/wan_t2v_A14B.py`` (``boundary`` 0.875) and ComfyUI's Wan2.2
14B text-to-video template with the 4-step LoRAs (two ``KSamplerAdvanced`` on
``euler`` / ``simple`` over the flow table at the graph's shift: the first two
steps on the high-noise expert, the last two on the low-noise one, the second
run continuing from the first's state; ``W + strength · (alpha / rank) · up @
down`` on ten linears a block).

The arithmetic policy is ``reference_sd``'s, the tower ``reference_t5``'s,
the Euler loop and the flow table ``reference_mmdit``'s / ``reference_zimage``'s
(imported, not copied): float32 as the six-term sum over bfloat16 pieces,
``bfloat16`` operands with float32 accumulation as the stated precision,
``int8`` operands as the control. It reads only the files the benchmark wrote
and computes nothing with the program.

So that it fits beside nothing but itself on one chip: the tower's tensors
stay on the host as views over its file and go to the device one block at a
time (``reference_t5``); the self-attention runs one head and one block of
queries at a time; the decoder runs one FRAME at a time through every layer,
each causal convolution as one 2-D convolution over its three frames laid
along the channels (the same sums). Departures from the published code, each
below bfloat16 resolution: LayerNorm, RMS-norm and softmax statistics in
float32 whatever the mode; a LoRA is added to its kernel in float32 and the
sum rounded once to the file's bfloat16, as a loader that keeps 16-bit
weights must; at CFG 1.0 the negative prompt conditions nothing and is not
encoded.
"""

from __future__ import annotations

import gc
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import reference_sd as sd
from . import reference_t5, safetensors_io, synth
from .layout_wan import LORA_TARGETS
from .reference_mmdit import _gelu_tanh, _norm, sample_euler
from .reference_sd import F32, PRECISIONS
from .reference_zimage import simple_sigmas


NEEDS = frozenset({"wan-depth-from-file", "residency"})


def refuse_a_program_that_cannot_serve_this(config: dict) -> None:
    """The reference alone is minutes on the chip, and a checkout whose
    loaders cannot serve this configuration — two experts at the depth their
    files have, resident in 16 bits, beside a tower that has to leave the
    chip — would fail only after it, or run out of memory. So ask what the
    program STATES of its loaders (``models/loader.CAPABILITIES``, names and
    nothing else) before the first tensor is read, and leave with a message
    and a non-zero exit code. The one thing here that looks at the program;
    where there is no program (the benchmark's own tests) nothing is asked."""
    try:
        from comfyui_parallelanything_tpu.models import loader
    except ImportError:
        return
    missing = NEEDS - frozenset(getattr(loader, "CAPABILITIES", ()))
    if missing:
        raise SystemExit(
            f"benchmark: {config['name']} needs loaders that state "
            f"{sorted(NEEDS)}; this checkout's do not state {sorted(missing)} "
            "(a WAN expert in float32 at its preset's depth, no model ever "
            "moved off the chip): the configuration cannot run here and "
            "nothing was run")


# -- the denoiser ---------------------------------------------------------------


def rope_tables(frames: int, hp: int, wp: int, d: int, theta: float = 10000.0):
    """``rope_params`` / ``rope_apply``: a head's ``d / 2`` complex pairs are
    split ``d/2 − 2·(d/6)`` / ``d/6`` / ``d/6`` between frame, row and column
    index (22 / 21 / 21 at d = 128); an axis with ``c`` pairs turns pair ``k``
    by index · theta^(−2k / 2c). → cos, sin (S, d / 2), tokens in (frame, row,
    column) order."""
    c = d // 2
    split = (c - 2 * (c // 3), c // 3, c // 3)
    grid = np.stack(np.meshgrid(np.arange(frames), np.arange(hp), np.arange(wp),
                                indexing="ij"), axis=-1).reshape(-1, 3)
    parts = []
    for axis, pairs in enumerate(split):
        omega = 1.0 / theta ** (np.arange(0, 2 * pairs, 2, dtype=np.float64) / (2 * pairs))
        parts.append(grid[:, axis:axis + 1].astype(np.float64) * omega[None])
    ang = np.concatenate(parts, axis=-1)
    return jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)


def _rope(x, cos, sin):
    """(B, S, H, D): each consecutive pair (x0, x1) becomes
    (cos·x0 − sin·x1, sin·x0 + cos·x1)."""
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    x0, x1 = pairs[..., 0], pairs[..., 1]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.stack([c * x0 - s * x1, s * x0 + c * x1], axis=-1).reshape(x.shape)


def _rms(x, scale, eps):
    return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale.astype(F32)


def _lin(p, w, key, x):
    return sd._linear(p, x, w[f"{key}.weight"], w.get(f"{key}.bias"))


QUERY_BLOCK = 4096


def _attention(p, q, k, v):
    """(B, Sq, H, D) x (B, Sk, H, D) → (B, Sq, H·D): softmax(q kᵀ / √D) v, one
    head and one block of queries at a time (20,280 x 20,280 logits a head
    are 1.6 GB in float32); the two products on bfloat16 operands under every
    mode below float32."""
    p = "float32" if p == "float32" else "bfloat16"
    b, sq, h, d = q.shape
    blocks = next(n for n in range(-(-sq // QUERY_BLOCK), sq + 1) if sq % n == 0)

    def head(qkv):
        qh, kh, vh = qkv

        def rows(qb):
            logits = sd._ein(p, "bqd,bkd->bqk", qb, kh) * (d ** -0.5)
            return sd._ein(p, "bqk,bkd->bqd", jax.nn.softmax(logits, axis=-1), vh)

        out = lax.map(rows, jnp.moveaxis(qh.reshape(b, blocks, sq // blocks, d), 1, 0))
        return jnp.moveaxis(out, 0, 1).reshape(b, sq, d)

    out = lax.map(head, tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v)))
    return jnp.moveaxis(out, 0, 2).reshape(b, sq, h * d)


def _block(p, heads, eps, w, x, ctx, e0, cos, sin):
    """One ``WanAttentionBlock`` on tokens (B, S, dim), projected text
    (B, L, dim) and the six time vectors ``e0`` (B, 6, dim), float32."""
    mod = w["modulation"].astype(F32) + e0
    s1, c1, g1, s2, c2, g2 = (mod[:, i:i + 1] for i in range(6))
    b, s, dim = x.shape

    def heads_of(t):
        return t.reshape(t.shape[0], t.shape[1], heads, dim // heads)

    h = _norm(x, eps) * (1.0 + c1) + s1
    q = _rms(_lin(p, w, "self_attn.q", h), w["self_attn.norm_q.weight"], eps)
    k = _rms(_lin(p, w, "self_attn.k", h), w["self_attn.norm_k.weight"], eps)
    v = _lin(p, w, "self_attn.v", h)
    a = _attention(p, _rope(heads_of(q), cos, sin), _rope(heads_of(k), cos, sin),
                   heads_of(v))
    x = x + g1 * _lin(p, w, "self_attn.o", a)

    h = sd._layer_norm(x, w["norm3.weight"], w["norm3.bias"], eps)
    q = _rms(_lin(p, w, "cross_attn.q", h), w["cross_attn.norm_q.weight"], eps)
    k = _rms(_lin(p, w, "cross_attn.k", ctx), w["cross_attn.norm_k.weight"], eps)
    v = _lin(p, w, "cross_attn.v", ctx)
    x = x + _lin(p, w, "cross_attn.o", _attention(p, heads_of(q), heads_of(k), heads_of(v)))

    h = _norm(x, eps) * (1.0 + c2) + s2
    return x + g2 * _lin(p, w, "ffn.2", _gelu_tanh(_lin(p, w, "ffn.0", h)))


def _embed(p, freq_dim, w, x, t, context):
    """NCTHW latent → tokens through the patch ``Conv3d`` (its kernel is its
    stride: a linear over each patch's (c, pt, ph, pw) features); the text
    through its embedder; ``e`` and ``e0`` in float32, as published."""
    n, ch, f, hh, ww = x.shape
    pw_ = w["patch_embedding.weight"]
    pt, ph, pw = pw_.shape[2:]
    tok = x.reshape(n, ch, f // pt, pt, hh // ph, ph, ww // pw, pw)
    tok = tok.transpose(0, 2, 4, 6, 1, 3, 5, 7).reshape(
        n, (f // pt) * (hh // ph) * (ww // pw), ch * pt * ph * pw)
    tok = sd._linear(p, tok, pw_.reshape(pw_.shape[0], -1), w["patch_embedding.bias"])
    ctx = _lin(p, w, "text_embedding.2", _gelu_tanh(_lin(p, w, "text_embedding.0", context)))
    # the time path in float32 whatever the mode (published: under autocast
    # float32; the program's modules likewise)
    temb = sd.timestep_embedding(1000.0 * t, freq_dim)
    e = _lin("float32", w, "time_embedding.2",
             sd._silu(_lin("float32", w, "time_embedding.0", temb)))
    e0 = _lin("float32", w, "time_projection.1", sd._silu(e))
    return tok, ctx, e, e0.reshape(n, 6, -1)


def _head(p, eps, shape, w, x, e):
    mod = w["head.modulation"].astype(F32) + e[:, None, :]
    s, c = mod[:, 0:1], mod[:, 1:2]
    out = _lin("float32", w, "head.head", _norm(x, eps) * (1.0 + c) + s)
    n, ch, f, hh, ww = shape
    pt, ph, pw = 1, 2, 2
    out = out.reshape(n, f // pt, hh // ph, ww // pw, pt, ph, pw, ch)
    return out.transpose(0, 7, 1, 4, 2, 5, 3, 6).reshape(n, ch, f, hh, ww)


def wan(p, w, m: dict, x, t, context):
    """``WanModel.forward`` on an NCTHW latent ``x``, flow times ``t`` in
    [0, 1] and text states ``context`` (B, text_len, text_dim), block by
    block. Returns the velocity, NCTHW."""
    heads, eps = m["num_heads"], float(m["eps"])
    _, _, f, hh, ww = x.shape
    cos, sin = rope_tables(f, hh // 2, ww // 2, m["dim"] // heads)
    top = {k: v for k, v in w.items() if not k.startswith("blocks.")}
    tok, ctx, e, e0 = sd._jitted(_embed, p, m["freq_dim"])(top, x, t, context)
    block = sd._jitted(_block, p, heads, eps)
    for i in range(m["num_layers"]):
        tok = block(sd._sub(w, f"blocks.{i}."), tok, ctx, e0, cos, sin)
    return sd._jitted(_head, p, eps, x.shape)(top, tok, e)


def bake_lora(w: dict, lora: dict, strength: float) -> dict:
    """``W + strength · (alpha / rank) · up @ down`` on every linear the LoRA
    file names (``diffusion_model.blocks.N.<linear>``), the sum in float32 and
    rounded once to the kernel's own type."""
    out = dict(w)
    for key in w:
        if not key.endswith(".weight"):
            continue
        base = "diffusion_model." + key[: -len(".weight")]
        if f"{base}.lora_down.weight" not in lora:
            continue
        if not any(base.endswith("." + t) for t in LORA_TARGETS):
            raise ValueError(f"the reference knows no LoRA on {base}")
        down = jnp.asarray(np.asarray(lora[f"{base}.lora_down.weight"])).astype(F32)
        up = jnp.asarray(np.asarray(lora[f"{base}.lora_up.weight"])).astype(F32)
        alpha = float(np.asarray(lora[f"{base}.alpha"]).astype(np.float32))
        delta = jnp.matmul(up, down, precision=lax.Precision.HIGHEST)
        scale = strength * alpha / down.shape[0]
        # one kernel at a time: dispatch runs ahead of the device, and fifty
        # kernels' float32 temporaries in flight at once do not fit the chip
        out[key] = (w[key].astype(F32) + scale * delta).astype(
            w[key].dtype).block_until_ready()
    return out


# -- the decoder -------------------------------------------------------------------


def _vae_rms(x, gamma, bias=None):
    """``RMS_norm``: ``F.normalize(x, dim=channel) · √C · γ`` at each position
    (NCHW frame)."""
    norm = jnp.sqrt((x * x).sum(1, keepdims=True))
    y = x / jnp.maximum(norm, 1e-12) * math.sqrt(x.shape[1]) * gamma.astype(F32).reshape(1, -1, 1, 1)
    return y if bias is None else y + bias.astype(F32).reshape(1, -1, 1, 1)


def _conv3(p, w, b, two_before, one_before, frame):
    """One output frame of a causal (3, k, k) convolution: the three frames
    laid along the channels under the kernel's three time slices laid the same
    way — one 2-D convolution, the same sums."""
    x = jnp.concatenate([two_before, one_before, frame], axis=1)
    wk = jnp.concatenate([w[:, :, 0], w[:, :, 1], w[:, :, 2]], axis=1)
    return sd._conv(p, x, wk, b)


def _vae_res(p, w, x, a2, a1, b2, b1):
    """``ResidualBlock`` on one frame ``x`` (1, C, H, W). ``a2, a1`` are the
    first convolution's inputs of the two frames before, ``b2, b1`` the
    second's. → (out, this frame's two convolution inputs)."""
    a = sd._silu(_vae_rms(x, w["residual.0.gamma"]))
    y = _conv3(p, w["residual.2.weight"], w["residual.2.bias"], a2, a1, a)
    b = sd._silu(_vae_rms(y, w["residual.3.gamma"]))
    y = _conv3(p, w["residual.6.weight"], w["residual.6.bias"], b2, b1, b)
    if "shortcut.weight" in w:
        x = sd._conv(p, x, w["shortcut.weight"][:, :, 0], w["shortcut.bias"])
    return x + y, a, b


def _vae_attn(p, w, x):
    """``AttentionBlock``: one head over a frame's positions."""
    n, c, hh, ww = x.shape
    qkv = sd._conv(p, _vae_rms(x, w["norm.gamma"]), w["to_qkv.weight"], w["to_qkv.bias"])
    q, k, v = (t.reshape(n, c, hh * ww).transpose(0, 2, 1) for t in jnp.split(qkv, 3, axis=1))
    a = sd._attention(p, q, k, v, 1).transpose(0, 2, 1).reshape(n, c, hh, ww)
    return x + sd._conv(p, a, w["proj.weight"], w["proj.bias"])


def _vae_in(p, mean, std, w, z, z2, z1):
    """Latent frame → ``conv2`` (1x1x1) → ``decoder.conv1`` (causal): returns
    the frame and the convolution's input to carry."""
    shape = (1, -1, 1, 1)
    z = z * jnp.asarray(std, F32).reshape(shape) + jnp.asarray(mean, F32).reshape(shape)
    u = sd._conv(p, z, w["conv2.weight"][:, :, 0], w["conv2.bias"])
    return _conv3(p, w["decoder.conv1.weight"], w["decoder.conv1.bias"], z2, z1, u), u


def _vae_time(p, w, x, x2, x1):
    """``time_conv`` (3, 1, 1) to twice the width → the frame's two frames."""
    y = _conv3(p, w["time_conv.weight"], w["time_conv.bias"], x2, x1, x)
    return jnp.split(y, 2, axis=1)


def _vae_up(p, w, x):
    """Nearest x2 in space, then the 3 x 3 ``Conv2d`` that halves the width."""
    x = jnp.repeat(jnp.repeat(x, 2, axis=2), 2, axis=3)
    return sd._conv(p, x, w["resample.1.weight"], w["resample.1.bias"])


def _vae_head(p, w, x, a2, a1):
    a = sd._silu(_vae_rms(x, w["decoder.head.0.gamma"]))
    y = _conv3(p, w["decoder.head.2.weight"], w["decoder.head.2.bias"], a2, a1, a)
    return jnp.clip(y, -1.0, 1.0), a


class _Carried:
    """The last two inputs of every causal convolution, by a name; zeros
    before the clip's first frame."""

    def __init__(self):
        self._state: dict[str, tuple] = {}

    def of(self, name: str, like):
        return self._state.get(name) or (jnp.zeros_like(like), jnp.zeros_like(like))

    def push(self, name: str, like, new):
        self._state[name] = (self.of(name, like)[1], new)


def wan_vae_decode(p, w, v: dict, z, last_frame: int | None = None):
    """The Wan2.1 decoder on a normalised latent clip ``z`` (1, z, T, h, w) →
    pixel frames (4(T − 1) + 1, 3, 8h, 8w) in [−1, 1], one frame at a time.
    ``last_frame``: stop after that pixel frame (the decoder is causal:
    nothing after a frame moves it)."""
    n_stage = len(v["dim_mult"])
    up_t = list(v["temperal_downsample"])[::-1]
    blocks = v["num_res_blocks"] + 1
    carried = _Carried()
    res = sd._jitted(_vae_res, p)
    out = []

    def res_block(name, x):
        wb = sd._sub(w, name + ".")
        a_like = x
        a2, a1 = carried.of(name + ".a", a_like)
        o_ch = wb["residual.2.weight"].shape[0]
        b_like = jnp.zeros((x.shape[0], o_ch) + x.shape[2:], F32)
        b2, b1 = carried.of(name + ".b", b_like)
        y, a, b = res(wb, x, a2, a1, b2, b1)
        carried.push(name + ".a", a_like, a)
        carried.push(name + ".b", b_like, b)
        return y

    def tail(stage: int, x, first: bool):
        """Stages ``stage`` … of the decoder on ONE frame, then the head."""
        seq = stage * (blocks + 1)
        for s in range(stage, n_stage):
            for _ in range(blocks):
                x = res_block(f"decoder.upsamples.{seq}", x)
                seq += 1
            if s == n_stage - 1:
                break
            wu = sd._sub(w, f"decoder.upsamples.{seq}.")
            seq += 1
            frames = [x]
            if up_t[s] and not first:
                name = f"decoder.upsamples.{seq - 1}.time"
                x2, x1 = carried.of(name, x)
                frames = sd._jitted(_vae_time, p)(wu, x, x2, x1)
                carried.push(name, x, x)
            ups = [sd._jitted(_vae_up, p)(wu, f) for f in frames]
            if len(ups) == 2:
                tail(s + 1, ups[0], first)
                x = ups[1]
            else:
                x = ups[0]
            if last_frame is not None and len(out) > last_frame:
                return
        a2, a1 = carried.of("head", x)
        y, a = sd._jitted(_vae_head, p)(
            {k: t for k, t in w.items() if k.startswith("decoder.head.")}, x, a2, a1)
        carried.push("head", x, a)
        out.append(y)

    top = {k: t for k, t in w.items() if k.startswith(("conv2.", "decoder.conv1."))}
    for t in range(z.shape[2]):
        zt = z[:, :, t]
        z2, z1 = carried.of("in", zt)
        x, u = sd._jitted(_vae_in, p, tuple(v["latents_mean"]), tuple(v["latents_std"]))(
            top, zt, z2, z1)
        carried.push("in", zt, u)
        x = res_block("decoder.middle.0", x)
        x = sd._jitted(_vae_attn, p)(sd._sub(w, "decoder.middle.1."), x)
        x = res_block("decoder.middle.2", x)
        tail(0, x, first=t == 0)
        if last_frame is not None and len(out) > last_frame:
            break
    frames = jnp.concatenate(out, axis=0)
    # ``tail`` calls itself, so these closures are a reference cycle that only
    # the collector frees: empty what they hold (gigabytes of carried frames at
    # full size), or a second and a third pass meet the first's on the chip.
    carried._state.clear()
    out.clear()
    return frames


# -- the whole served path ------------------------------------------------------------


def describe(graph: dict) -> dict:
    """What ComfyUI's Wan2.2 14B text-to-video graph with the 4-step LoRAs
    asks for, read off the graph as sent: two ``KSamplerAdvanced`` — the first
    adds the noise, starts at step 0, hands its latent on with the leftover
    noise at the step where the expert changes; the second adds none and runs
    from that step to the end — each on a ``UNETLoader``'s expert behind one
    ``LoraLoaderModelOnly`` and one ``ModelSamplingSD3`` (its shift), an
    ``EmptyHunyuanLatentVideo``, two prompts encoded by the tower of one
    ``CLIPLoader`` of type wan, one untiled ``VAEDecode`` on a ``VAELoader``'s
    autoencoder."""
    def node(ref):
        return graph[ref[0]]

    ks = {i: n["inputs"] for i, n in graph.items() if n["class_type"] == "KSamplerAdvanced"}
    if len(ks) != 2:
        raise ValueError("the reference reads graphs with exactly two KSamplerAdvanced")
    second_id = next((i for i, k in ks.items() if k["latent_image"][0] in ks), None)
    if second_id is None:
        raise ValueError("the second KSamplerAdvanced has to continue the first's latent")
    second = ks[second_id]
    first = ks[second["latent_image"][0]]
    steps, switch = int(first["steps"]), int(first["end_at_step"])
    if (first["add_noise"], first["return_with_leftover_noise"], int(first["start_at_step"])) \
            != ("enable", "enable", 0) or second["add_noise"] != "disable" \
            or second["return_with_leftover_noise"] != "disable" \
            or int(second["start_at_step"]) != switch or int(second["steps"]) != steps \
            or int(second["end_at_step"]) < steps or not 0 < switch < steps:
        raise ValueError("the reference reads the template's two-window split only")
    for a in ("cfg", "sampler_name", "scheduler"):
        if first[a] != second[a]:
            raise ValueError(f"the two samplers differ in {a}")
    latent = node(first["latent_image"])
    if latent["class_type"] != "EmptyHunyuanLatentVideo" or latent["inputs"].get("batch_size", 1) != 1:
        raise ValueError("the reference reads one EmptyHunyuanLatentVideo clip a request")
    experts = []
    for k in (first, second):
        patch = node(k["model"])
        if patch["class_type"] != "ModelSamplingSD3":
            raise ValueError(f"the reference does not know {patch['class_type']}")
        lora = node(patch["inputs"]["model"])
        if lora["class_type"] != "LoraLoaderModelOnly":
            raise ValueError(f"the reference does not know {lora['class_type']}")
        unet = node(lora["inputs"]["model"])
        if unet["class_type"] != "UNETLoader":
            raise ValueError(f"the reference does not know {unet['class_type']}")
        experts.append({"unet": unet["inputs"]["unet_name"],
                        "lora": lora["inputs"]["lora_name"],
                        "strength": float(lora["inputs"]["strength_model"]),
                        "shift": float(patch["inputs"]["shift"])})
    if experts[0]["shift"] != experts[1]["shift"]:
        raise ValueError("the two experts are patched with different shifts")
    dec = [n for n in graph.values() if n["class_type"].startswith("VAEDecode")
           and n["inputs"]["samples"][0] == second_id]
    if [n["class_type"] for n in dec] != ["VAEDecode"] \
            or node(dec[0]["inputs"]["vae"])["class_type"] != "VAELoader":
        raise ValueError("the reference reads graphs with one untiled VAEDecode "
                         "on a VAELoader's autoencoder")
    texts = [node(first["positive"]), node(first["negative"])]
    loaders = [node(t["inputs"]["clip"]) for t in texts]
    if any(ld["class_type"] != "CLIPLoader" or ld["inputs"].get("type") != "wan"
           for ld in loaders):
        raise ValueError("the reference reads prompts encoded through a "
                         "CLIPLoader of type wan")
    return {"seed": first["noise_seed"], "steps": steps, "switch_step": switch,
            "cfg": first["cfg"], "sampler_name": first["sampler_name"],
            "scheduler": first["scheduler"], "shift": experts[0]["shift"],
            "experts": experts, "lora_strengths": [e["strength"] for e in experts],
            "positive": texts[0]["inputs"]["text"], "negative": texts[1]["inputs"]["text"],
            "clip_name": loaders[0]["inputs"]["clip_name"],
            "vae_name": node(dec[0]["inputs"]["vae"])["inputs"]["vae_name"],
            "width": latent["inputs"]["width"], "height": latent["inputs"]["height"],
            "frames": (int(latent["inputs"]["length"]) - 1) // 4 * 4 + 1}


class Reference:
    """The served path of one configuration in one arithmetic. An expert goes
    to the device in its file's own type with its LoRA baked, one at a time,
    and is dropped when the sampler passes the step where the expert changes;
    the tower stays on the host (above); the decoder goes last."""

    def __init__(self, config: dict, checkpoint: str, tokenizer, precision: str,
                 tokenizers: dict | None = None, files: dict | None = None):
        if precision not in PRECISIONS:
            raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
        refuse_a_program_that_cannot_serve_this(config)
        self.c, self.p = config, precision
        self.toks = tokenizers or {}
        # the graph names files by their base names, as the stock loaders do
        self._paths = {os.path.basename(spec["file"]): (files or {}).get(spec["file"], checkpoint)
                       for spec in synth.checkpoint_files(config)}

    def _views(self, name: str) -> dict:
        return safetensors_io.read(self._paths[os.path.basename(name)])

    def text_states(self, ids, clip_name: str):
        """The tower's final states for ids (N, text_len): padded keys masked
        inside, the rows after the valid tokens zeroed on the way out; a
        method of its own so that a test can put a broken tower in its place."""
        mask = np.asarray(ids) != 0
        states = reference_t5.encode(
            self.p, self._views(clip_name), dict(self.c["text_t5"], per_layer_bias=True),
            ids, mask)
        # Waited for: dispatch runs ahead of the device, each block's call
        # holds that block's weights until it has run, and an expert loaded
        # beside 11 GB of blocks still in flight does not fit the chip.
        return (states.astype(F32) * jnp.asarray(mask, F32)[..., None]).block_until_ready()

    def expert(self, spec: dict) -> dict:
        w = sd.load_weights(self._views(spec["unet"]))
        return bake_lora(w, self._views(spec["lora"]), spec["strength"])

    def latent(self, req: dict):
        """The sampled latent of a request, NCTHW: Euler on the velocity over
        the ``simple`` picks of the flow table, the expert changed at
        ``switch_step``."""
        c, p, m = self.c, self.p, self.c["wan"]
        if (req["sampler_name"], req["scheduler"]) != ("euler", "simple"):
            raise NotImplementedError(
                f"reference has no {req['sampler_name']}/{req['scheduler']}")
        if float(req["cfg"]) != 1.0:
            raise NotImplementedError("the reference samples without guidance (CFG 1.0)")
        ids = np.stack([self.toks["t5"].ids(req["positive"])])
        context = self.text_states(ids, req["clip_name"])
        f = (req["frames"] - 1) // 4 + 1
        h8, w8 = req["height"] // 8, req["width"] // 8
        ch = c["vae"]["z_dim"]
        # The served path draws the clip's noise as one (1, f, h, w, c) array
        # from jax.random.key(seed): draw it likewise.
        noise = jax.random.normal(jax.random.key(int(req["seed"]) % 2 ** 63),
                                  (1, f, h8, w8, ch), F32)
        x = jnp.transpose(noise, (0, 4, 1, 2, 3))
        sigmas = simple_sigmas(req["steps"], req["shift"])
        x = x * float(sigmas[0])  # sigma_max is 1: the flow's start is the noise
        k = req["switch_step"]
        for spec, window in zip(req["experts"], (sigmas[: k + 1], sigmas[k:])):
            w = self.expert(spec)

            def velocity(x, sigma, w=w):
                return wan(p, w, m, x, jnp.full((1,), sigma, F32), context)

            x = sample_euler(velocity, x, window).block_until_ready()
            del w, velocity
        return x

    def images(self, req: dict, rows: list[int]) -> np.ndarray:
        """Float images in [0, 1], (len(rows), H, W, 3): the pixel frames
        ``rows`` of one request's clip (``describe``'s keys)."""
        gc.collect()  # a pass before this one leaves nothing on the device
        z = self.latent(req)
        w = sd.load_weights(self._views(req["vae_name"]))
        frames = wan_vae_decode(self.p, w, self.c["vae"], z, last_frame=max(rows))
        imgs = jnp.clip(frames[jnp.asarray(rows)] * 0.5 + 0.5, 0.0, 1.0)
        return np.asarray(jnp.transpose(imgs, (0, 2, 3, 1)), np.float32)
