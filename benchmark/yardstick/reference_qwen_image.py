"""Plain reference of the served Qwen-Image path, from the published
descriptions: ``transformer_qwenimage.py``, ``pipeline_qwenimage.py`` and
``autoencoder_kl_qwenimage.py`` of huggingface/diffusers with
``transformer/config.json``, ``text_encoder/config.json`` (Qwen2.5-VL-7B-
Instruct) and ``vae/config.json`` of Qwen/Qwen-Image, and ComfyUI's Qwen-Image
text-to-image template with the 8-step LoRA of lightx2v/Qwen-Image-Lightning.

The denoiser (``qwen_image``): the latent's 2 x 2 patches as tokens in
row-major order, features ordered (c, ph, pw), through ``img_in``; the tower's
states through ``txt_norm`` (RMS, ε 1e-6, learned scale) and ``txt_in``;
``temb = Linear(SiLU(Linear(sincos_256(1000 σ))))``, cosine half first;
rotary tables of three axes (θ 10000, 16 / 56 / 56 of the 128-wide head,
adjacent pairs) over positions CENTRED on the image — the patch at row i,
column j of an h x w grid at (0, i − (h − ⌊h/2⌋), j − (w − ⌊w/2⌋)), text
token n at (p, p, p), p = max(⌊h/2⌋, ⌊w/2⌋) + n; ``num_layers`` blocks of two
streams with weights apart: ``(sh1, sc1, g1, sh2, sc2, g2) =
Linear(SiLU(temb))`` a stream, ``a = LN(s)(1 + sc1) + sh1``, q / k / v with
bias, q and k RMS-normed per head, each stream turned by its own positions,
ONE softmax attention over text ⊕ image (text first), ``s += g1 ·
W_out(attn_s)``, ``s += g2 · W2(GELU_tanh(W1(LN(s)(1 + sc2) + sh2)))``; the
head ``proj_out(LN(img)(1 + scale) + shift)`` with ``(scale, shift) =
Linear(SiLU(temb))``, SCALE first. The output is the velocity.

The tower (``qwen25vl_states``): ``reference_zimage``'s causal-tower
arithmetic (its RMS norm, linear, half-split rotary at θ 1e6 and the grouped
causal softmax, imported) in Qwen2's layer: biases on q / k / v, NO per-head
norms of q and k, and the LAST layer's state through ``model.norm`` where
Z-Image takes the state before the last layer. The prompt is wrapped in the
pipeline's template (a SYSTEM prompt, then the user's turn, then the
assistant's opening) and the states from the first token of the prompt to
the last token are kept: everything through ``<|im_start|>user\\n`` is cut
off, found by POSITION (the second ``<|im_start|>`` and the two tokens after
it) because the seeded table splits the system prompt into another count
than the published table's 34.

The decoder: ``AutoencoderKLQwenImage`` is the Wan2.1 causal 3-D decoder
(``reference_wan.wan_vae_decode``) on a clip of ONE latent frame:
``z · std + mean``, the first-frame path of every causal convolution and
up-sampler, pixels clamped to [−1, 1] — written out here for that ONE frame
(``decode_frame``: ``reference_wan``'s norm, up-sampler and query-blocked
attention imported; its frame-by-frame walk keeps every convolution's input
for the next frame, 7 GB at 1328² that a one-frame clip never reads, and
its middle attention writes 27,556 x 27,556 logits out whole).

Sampling: ComfyUI's ``simple`` scheduler over the flow table at the graph's
shift (``ModelSamplingAuraFlow`` 3.1), Euler on the velocity, no
classifier-free guidance: 8 forwards (``reference_zimage.simple_sigmas`` /
``reference_mmdit.sample_euler``, imported). The LoRA: ``W + strength · (alpha
/ rank) · up @ down`` on twelve linears a block, the sum in float32, rounded
once to the file's bfloat16, as a loader that keeps 16-bit weights must.

The arithmetic policy is ``reference_sd``'s: float32 as the six-term sum over
bfloat16 pieces, ``bfloat16`` operands with float32 accumulation as the
stated precision, ``int8`` operands as the control. It reads only the files
the benchmark wrote and computes nothing with the program. The tower (14.1 GB
in its file's bfloat16) stays on the host as views over the file and goes to
the device one layer at a time; the denoiser's file (5.5 GB at the cut) goes
whole, in its own type, after the tower has spoken.

Departures from the published code: LayerNorm, RMS-norm and softmax
statistics in float32 whatever the mode; attention one head at a time; at
CFG 1.0 the negative prompt conditions nothing and is not encoded; the
``visual`` tower does not exist here.
"""

from __future__ import annotations

import gc
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import reference_sd as sd
from . import reference_wan, safetensors_io, synth
from .layout_qwen_image import LORA_TARGETS
from .reference_mmdit import _attention, _gelu_tanh, _norm, sample_euler
from .reference_sd import F32, PRECISIONS
from .reference_zimage import (_lin, _rms, _rope, _rotate_half, rope_tables,
                               simple_sigmas)

NEEDS = frozenset({"residency", "qwen-image"})

TEMPLATE = (
    "<|im_start|>system\nDescribe the image by detailing the color, shape, size, "
    "texture, quantity, text, spatial relationships of the objects and "
    "background:<|im_end|>\n<|im_start|>user\n{}<|im_end|>\n<|im_start|>assistant\n")
TURN = "<|im_start|>"


def _refuse_a_program_that_cannot_serve_this() -> None:
    """The harness writes 20 GB and computes the reference (minutes on the
    chip) before it starts the server, so a checkout whose program has no
    Qwen-Image family — the parent of the PR that brought it — would fail
    only after them. Ask what the program STATES of its loaders
    (``models/loader.CAPABILITIES``, names and nothing else) once, at import,
    and leave at once with a message and a non-zero exit code. The one thing
    here that looks at the program; where there is no program (the
    benchmark's own tests) nothing is asked."""
    try:
        from comfyui_parallelanything_tpu.models import loader
    except ImportError:
        return
    missing = NEEDS - frozenset(getattr(loader, "CAPABILITIES", ()))
    if missing:
        raise SystemExit(
            f"benchmark: qwen-image needs loaders that state {sorted(NEEDS)}; "
            f"this checkout's do not state {sorted(missing)} (no double-stream "
            "Qwen-Image family, no Qwen2.5-VL tower, no one-frame path through "
            "the 3-D autoencoder): the configuration cannot run here and "
            "nothing was run")


_refuse_a_program_that_cannot_serve_this()


# -- the text tower ----------------------------------------------------------------


def _qwen2_layer(p, heads, kv_heads, eps, w, h, cos, sin):
    """``Qwen2_5_VLDecoderLayer`` on text: ``reference_zimage._qwen3_layer``
    without the q/k norms and with the biases the file carries."""
    b, s, _ = h.shape
    x = _rms(h, w["input_layernorm.weight"], eps)
    q = _lin(p, w, "self_attn.q_proj", x).reshape(b, s, heads, -1)
    k = _lin(p, w, "self_attn.k_proj", x).reshape(b, s, kv_heads, -1)
    v = _lin(p, w, "self_attn.v_proj", x).reshape(b, s, kv_heads, -1)
    q, k = _rotate_half(q, cos, sin), _rotate_half(k, cos, sin)
    d, group = q.shape[-1], heads // kv_heads
    mode = "float32" if p == "float32" else "bfloat16"
    qg = q.reshape(b, s, kv_heads, group, d)
    logits = sd._ein(mode, "bqhgd,bkhd->bhgqk", qg, k) * (d ** -0.5)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    att = sd._ein(mode, "bhgqk,bkhd->bqhgd", probs, v).reshape(b, s, heads * d)
    h = h + _lin(p, w, "self_attn.o_proj", att)
    x = _rms(h, w["post_attention_layernorm.weight"], eps)
    x = sd._silu(_lin(p, w, "mlp.gate_proj", x)) * _lin(p, w, "mlp.up_proj", x)
    return h + _lin(p, w, "mlp.down_proj", x)


def _final_norm(eps, w, h):
    return _rms(h, w["model.norm.weight"], eps)


def qwen25vl_states(p, w, c: dict, ids):
    """The last layer's states through ``model.norm`` (B, S, hidden) for token
    ids (B, S) with no padding; ``w`` the file's tensors (host views are fine:
    a layer's go to the device inside its call), ``c`` its sizes."""
    eps = float(c.get("rms_norm_eps", 1e-6))
    heads = c["num_attention_heads"]
    d, s = c["hidden_size"] // heads, np.asarray(ids).shape[1]
    omega = 1.0 / float(c["rope_theta"]) ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(s, dtype=np.float64)[:, None] * omega[None]
    cos, sin = jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)
    h = jnp.asarray(np.asarray(w["model.embed_tokens.weight"][np.asarray(ids)]), F32)
    layer = sd._jitted(_qwen2_layer, p, heads, c["num_key_value_heads"], eps)
    for i in range(c["num_hidden_layers"]):
        h = layer(sd._sub(w, f"model.layers.{i}."), h, cos, sin)
    return sd._jitted(_final_norm, eps)({"model.norm.weight": w["model.norm.weight"]}, h)


def prefix_length(ids, turn_id: int) -> int:
    """How many leading tokens stand before the user's text: through the
    SECOND ``<|im_start|>`` and the two tokens after it (``user``, a newline)."""
    turns = [i for i, t in enumerate(ids) if int(t) == turn_id]
    if len(turns) < 2:
        raise ValueError("the templated prompt has no second <|im_start|>")
    return turns[1] + 3


# -- the denoiser ------------------------------------------------------------------


def position_ids(txt_len: int, hp: int, wp: int) -> np.ndarray:
    """(txt_len + hp·wp, 3), text first: the centred grid and the text after
    its half-extent (``QwenEmbedRope``, ``scale_rope`` true)."""
    img = np.zeros((hp, wp, 3), np.int64)
    img[..., 1] = (np.arange(hp) - (hp - hp // 2))[:, None]
    img[..., 2] = (np.arange(wp) - (wp - wp // 2))[None, :]
    txt = max(hp // 2, wp // 2) + np.arange(txt_len)
    return np.concatenate([np.repeat(txt[:, None], 3, axis=1), img.reshape(-1, 3)])


def _mods(p, w, key, temb, n):
    return jnp.split(_lin(p, w, key, sd._silu(temb))[:, None, :], n, axis=-1)


def _block(p, heads, w, img, txt, temb, cos, sin):
    """One ``QwenImageTransformerBlock``; cos, sin (txt + img rows, 64)."""
    n_txt = txt.shape[1]
    names = {"img": ("to_q", "to_k", "to_v", "norm_q", "norm_k", "to_out.0"),
             "txt": ("add_q_proj", "add_k_proj", "add_v_proj", "norm_added_q",
                     "norm_added_k", "to_add_out")}
    rows = {"txt": slice(None, n_txt), "img": slice(n_txt, None)}
    mods, qkv = {}, {}
    for s, x in (("txt", txt), ("img", img)):
        mods[s] = m = _mods(p, w, f"{s}_mod.1", temb, 6)
        a = _norm(x) * (1.0 + m[1]) + m[0]
        q, k, v = (_lin(p, w, f"attn.{n}", a).reshape(*a.shape[:2], heads, -1)
                   for n in names[s][:3])
        c, sn = cos[rows[s]], sin[rows[s]]
        qkv[s] = (_rope(_rms(q, w[f"attn.{names[s][3]}.weight"], 1e-6), c, sn),
                  _rope(_rms(k, w[f"attn.{names[s][4]}.weight"], 1e-6), c, sn), v)
    q, k, v = (jnp.concatenate([qkv["txt"][i], qkv["img"][i]], axis=1) for i in range(3))
    att = _attention(p, q, k, v)
    out = []
    for s, x in (("img", img), ("txt", txt)):
        m = mods[s]
        x = x + m[2] * _lin(p, w, f"attn.{names[s][5]}", att[:, rows[s]])
        h = _lin(p, w, f"{s}_mlp.net.0.proj", _norm(x) * (1.0 + m[4]) + m[3])
        out.append(x + m[5] * _lin(p, w, f"{s}_mlp.net.2", _gelu_tanh(h)))
    return out[0], out[1]


def _embed(p, w, x, sigma, states):
    """NCHW latent → tokens ``(c ph pw)``-ordered through ``img_in``; the
    tower's states through ``txt_norm`` and ``txt_in``; ``temb``."""
    n, ch, hh, ww = x.shape
    tok = x.reshape(n, ch, hh // 2, 2, ww // 2, 2).transpose(0, 2, 4, 1, 3, 5)
    img = _lin(p, w, "img_in", tok.reshape(n, (hh // 2) * (ww // 2), ch * 4))
    txt = _lin(p, w, "txt_in", _rms(states, w["txt_norm.weight"], 1e-6))
    t = "time_text_embed.timestep_embedder"
    temb = _lin(p, w, f"{t}.linear_2", sd._silu(
        _lin(p, w, f"{t}.linear_1", sd.timestep_embedding(1000.0 * sigma, 256))))
    return img, txt, temb


def _final(p, shape, w, img, temb):
    scale, shift = _mods(p, w, "norm_out.linear", temb, 2)
    x = _lin(p, w, "proj_out", _norm(img) * (1.0 + scale) + shift)
    n, ch, hh, ww = shape
    x = x.reshape(n, hh // 2, ww // 2, ch, 2, 2)
    return x.transpose(0, 3, 1, 4, 2, 5).reshape(n, ch, hh, ww)


def qwen_image(p, w, m: dict, x, sigma, states):
    """``QwenImageTransformer2DModel.forward`` on NCHW ``x``, flow times
    ``sigma`` in (0, 1] and the tower's states (B, L, joint_attention_dim) of
    the kept tokens, block by block. Returns the velocity, NCHW."""
    heads = m["num_attention_heads"]
    if m["attention_head_dim"] != sum(m["axes_dims_rope"]):
        raise ValueError("the rotary axes do not fill the head")
    if m.get("guidance_embeds"):
        raise NotImplementedError("the reference has no guidance embedder")
    cos, sin = rope_tables(position_ids(states.shape[1], x.shape[2] // 2, x.shape[3] // 2),
                           m["axes_dims_rope"], 10000.0)
    top = {k: v for k, v in w.items() if not k.startswith("transformer_blocks.")}
    img, txt, temb = sd._jitted(_embed, p)(top, x, sigma, states)
    block = sd._jitted(_block, p, heads)
    for i in range(m["num_layers"]):
        img, txt = block(sd._sub(w, f"transformer_blocks.{i}."), img, txt, temb, cos, sin)
    return sd._jitted(_final, p, x.shape)(top, img, temb)


def bake_lora(w: dict, lora: dict, strength: float) -> dict:
    """``W + strength · (alpha / rank) · up @ down`` on every linear the LoRA
    file names, the sum in float32 and rounded once to the kernel's own type."""
    out = dict(w)
    bases = {k[: -len(".lora_down.weight")] for k in lora if k.endswith(".lora_down.weight")}
    for base in sorted(bases):
        if not any(base.endswith("." + t) for t in LORA_TARGETS):
            raise ValueError(f"the reference knows no LoRA on {base}")
        key = f"{base}.weight"
        down = jnp.asarray(np.asarray(lora[f"{base}.lora_down.weight"])).astype(F32)
        up = jnp.asarray(np.asarray(lora[f"{base}.lora_up.weight"])).astype(F32)
        alpha = float(np.asarray(lora[f"{base}.alpha"]).astype(np.float32))
        delta = jnp.matmul(up, down, precision=lax.Precision.HIGHEST)
        # one kernel at a time: dispatch runs ahead of the device
        out[key] = (w[key].astype(F32) + strength * alpha / down.shape[0] * delta).astype(
            w[key].dtype).block_until_ready()
    return out


# -- the decoder on one frame ------------------------------------------------------


def _conv_t0(p, w, b, x):
    """A causal (3, k, k) convolution on a clip's FIRST frame: the two frames
    before it are zeros, so only the kernel's last time slice meets anything."""
    return sd._conv(p, x, w[:, :, -1], b)


def _res_t0(p, w, x):
    """``ResidualBlock`` (RMS norm, SiLU, causal 3x3x3, twice; a 1x1x1
    shortcut where the widths differ) on the first frame."""
    y = sd._silu(reference_wan._vae_rms(x, w["residual.0.gamma"]))
    y = _conv_t0(p, w["residual.2.weight"], w["residual.2.bias"], y)
    y = sd._silu(reference_wan._vae_rms(y, w["residual.3.gamma"]))
    y = _conv_t0(p, w["residual.6.weight"], w["residual.6.bias"], y)
    if "shortcut.weight" in w:
        x = sd._conv(p, x, w["shortcut.weight"][:, :, 0], w["shortcut.bias"])
    return x + y


def _attn_t0(p, w, x):
    """``AttentionBlock``: one head over the frame's positions, a block of
    queries at a time (``reference_wan._attention``: 27,556 x 27,556 logits
    at 1328² are 3 GB a term of the float32 sum if written out whole)."""
    n, c, hh, ww = x.shape
    qkv = sd._conv(p, reference_wan._vae_rms(x, w["norm.gamma"]),
                   w["to_qkv.weight"], w["to_qkv.bias"])
    q, k, v = (t.reshape(n, c, hh * ww).transpose(0, 2, 1)[:, :, None, :]
               for t in jnp.split(qkv, 3, axis=1))
    a = reference_wan._attention(p, q, k, v).transpose(0, 2, 1).reshape(n, c, hh, ww)
    return x + sd._conv(p, a, w["proj.weight"], w["proj.bias"])


def _in_t0(p, mean, std, w, z):
    shape = (1, -1, 1, 1)
    z = z * jnp.asarray(std, F32).reshape(shape) + jnp.asarray(mean, F32).reshape(shape)
    z = sd._conv(p, z, w["conv2.weight"][:, :, 0], w["conv2.bias"])
    return _conv_t0(p, w["decoder.conv1.weight"], w["decoder.conv1.bias"], z)


def _head_t0(p, w, x):
    y = sd._silu(reference_wan._vae_rms(x, w["decoder.head.0.gamma"]))
    return jnp.clip(_conv_t0(p, w["decoder.head.2.weight"], w["decoder.head.2.bias"], y),
                    -1.0, 1.0)


def decode_frame(p, w, v: dict, z):
    """``AutoencoderKLQwenImage.decode`` of an image latent (1, z, h, w) →
    (1, 3, 8h, 8w) in [−1, 1]: the Wan2.1 causal 3-D decoder on a clip of ONE
    frame, written out for that frame alone — ``z · std + mean``, the 1x1x1
    ``conv2``, every causal convolution with zeros for the frames before
    (``_conv_t0``), the middle (residual, attention, residual), per stage
    ``num_res_blocks + 1`` residual blocks then nearest x2 and the 3 x 3
    ``Conv2d`` that halves the width — the first frame passes the temporal
    up-samplers as it is, no ``time_conv`` — and the head. It is
    ``reference_wan.wan_vae_decode``'s frame 0 (held to it at tiny sizes in
    ``tests/test_qwen_image_reference.py``) without that walk's carried frames:
    at 1328² they are 7 GB that a one-frame clip never reads."""
    n_stage, blocks = len(v["dim_mult"]), v["num_res_blocks"] + 1
    res, sub = sd._jitted(_res_t0, p), lambda key: sd._sub(w, key + ".")
    x = sd._jitted(_in_t0, p, tuple(v["latents_mean"]), tuple(v["latents_std"]))(
        {k: t for k, t in w.items() if k.startswith(("conv2.", "decoder.conv1."))}, z)
    x = res(sub("decoder.middle.0"), x)
    x = sd._jitted(_attn_t0, p)(sub("decoder.middle.1"), x)
    x = res(sub("decoder.middle.2"), x)
    seq = 0
    for stage in range(n_stage):
        for _ in range(blocks):
            x = res(sub(f"decoder.upsamples.{seq}"), x)
            seq += 1
        if stage != n_stage - 1:
            x = sd._jitted(reference_wan._vae_up, p)(sub(f"decoder.upsamples.{seq}"), x)
            seq += 1
    return sd._jitted(_head_t0, p)(
        {k: t for k, t in w.items() if k.startswith("decoder.head.")}, x)


# -- the whole served path ------------------------------------------------------------


def describe(graph: dict) -> dict:
    """What ComfyUI's Qwen-Image text-to-image graph with the 8-step LoRA asks
    for, read off the graph as sent: one ``KSampler`` whose model is a
    ``UNETLoader``'s behind one ``LoraLoaderModelOnly`` and one
    ``ModelSamplingAuraFlow`` (its shift), an ``EmptySD3LatentImage``, two
    prompts encoded by the tower of one ``CLIPLoader`` of type qwen_image, one
    untiled ``VAEDecode`` on a ``VAELoader``'s autoencoder."""
    def node(ref):
        return graph[ref[0]]

    ks = [(i, n) for i, n in graph.items() if n["class_type"] == "KSampler"]
    if len(ks) != 1:
        raise ValueError("the reference reads graphs with exactly one KSampler")
    ks_id, k = ks[0][0], ks[0][1]["inputs"]
    latent, patch = node(k["latent_image"]), node(k["model"])
    if latent["class_type"] != "EmptySD3LatentImage" or k.get("denoise", 1.0) != 1.0:
        raise ValueError("the reference reads 16-channel txt2img graphs only")
    if patch["class_type"] != "ModelSamplingAuraFlow":
        raise ValueError(f"the reference does not know {patch['class_type']}")
    lora = node(patch["inputs"]["model"])
    if lora["class_type"] != "LoraLoaderModelOnly":
        raise ValueError(f"the reference does not know {lora['class_type']}")
    unet = node(lora["inputs"]["model"])
    if unet["class_type"] != "UNETLoader":
        raise ValueError(f"the reference does not know {unet['class_type']}")
    dec = [n for n in graph.values() if n["class_type"].startswith("VAEDecode")
           and n["inputs"]["samples"][0] == ks_id]
    if [n["class_type"] for n in dec] != ["VAEDecode"] \
            or node(dec[0]["inputs"]["vae"])["class_type"] != "VAELoader":
        raise ValueError("the reference reads graphs with one untiled VAEDecode "
                         "on a VAELoader's autoencoder")
    texts = [node(k["positive"]), node(k["negative"])]
    loaders = [node(t["inputs"]["clip"]) for t in texts]
    if any(t["class_type"] != "CLIPTextEncode" for t in texts) or any(
            ld["class_type"] != "CLIPLoader" or ld["inputs"].get("type") != "qwen_image"
            for ld in loaders):
        raise ValueError("the reference reads prompts encoded by CLIPTextEncode "
                         "through a CLIPLoader of type qwen_image")
    return {"seed": k["seed"], "steps": k["steps"], "cfg": k["cfg"],
            "sampler_name": k["sampler_name"], "scheduler": k["scheduler"],
            "shift": float(patch["inputs"]["shift"]),
            "unet": unet["inputs"]["unet_name"], "lora": lora["inputs"]["lora_name"],
            "lora_strength": float(lora["inputs"]["strength_model"]),
            "clip_name": loaders[0]["inputs"]["clip_name"],
            "vae_name": node(dec[0]["inputs"]["vae"])["inputs"]["vae_name"],
            "positive": texts[0]["inputs"]["text"],
            "negative": texts[1]["inputs"]["text"], **latent["inputs"]}


class Reference:
    """The served path of one configuration in one arithmetic: the tower from
    the host, a layer at a time; then the denoiser in its file's own type with
    the LoRA baked; the decoder last. ``tokenizer`` (CLIP's, which the harness
    always writes) is not used: the prompt goes through ``tokenizers["qwen"]``."""

    def __init__(self, config: dict, checkpoint: str, tokenizer, precision: str,
                 tokenizers: dict | None = None, files: dict | None = None):
        if precision not in PRECISIONS:
            raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
        self.c, self.p = config, precision
        self.toks = tokenizers or {}
        # the graph names files by their base names, as the stock loaders do
        self._paths = {os.path.basename(spec["file"]): (files or {}).get(spec["file"], checkpoint)
                       for spec in synth.checkpoint_files(config)}

    def _views(self, name: str) -> dict:
        return safetensors_io.read(self._paths[os.path.basename(name)])

    def tower_states(self, ids, clip_name: str):
        """The tower's last normed states for ids (N, S) with no padding; a
        method of its own so that a test can put a broken tower in its place."""
        return qwen25vl_states(self.p, self._views(clip_name), self.c["text"], ids)

    def encode(self, text: str, clip_name: str):
        """→ (1, kept tokens, hidden): the templated prompt's states from the
        first token of ``text`` on."""
        tok = self.toks["qwen"]
        ids = np.asarray(tok.pieces(TEMPLATE.format(text)), np.int32)
        start = prefix_length(ids, tok.special[TURN])
        states = self.tower_states(ids[None], clip_name)
        # Waited for: each layer's call holds that layer's weights until it
        # has run, and the denoiser loads next.
        return states[:, start:].astype(F32).block_until_ready()

    def denoiser(self, req: dict) -> dict:
        w = sd.load_weights(self._views(req["unet"]))
        return bake_lora(w, self._views(req["lora"]), req["lora_strength"])

    def latent(self, req: dict, rows: list[int]):
        c, p, m = self.c, self.p, self.c["transformer"]
        if (req["sampler_name"], req["scheduler"]) != ("euler", "simple"):
            raise NotImplementedError(
                f"reference has no {req['sampler_name']}/{req['scheduler']}")
        if float(req["cfg"]) != 1.0:
            raise NotImplementedError("the reference samples without guidance (CFG 1.0)")
        states = self.encode(req["positive"], req["clip_name"])
        h8, w8 = req["height"] // 8, req["width"] // 8
        # The served path draws the whole batch's noise as one NHWC array
        # from jax.random.key(seed): draw it likewise, keep the sampled rows.
        noise = jax.random.normal(jax.random.key(int(req["seed"]) % 2 ** 63),
                                  (req["batch_size"], h8, w8, c["vae"]["z_dim"]), F32)
        noise = jnp.transpose(noise[jnp.asarray(rows)], (0, 3, 1, 2))
        w = self.denoiser(req)

        def velocity(x, sigma):
            return qwen_image(p, w, m, x, jnp.full((1,), sigma, F32), states)

        sigmas = simple_sigmas(req["steps"], req["shift"])
        # sigma_max is 1: the flow's start is the noise itself. One row at a time.
        return [sample_euler(velocity, noise[k:k + 1] * float(sigmas[0]),
                             sigmas).block_until_ready() for k in range(len(rows))]

    def images(self, req: dict, rows: list[int]) -> np.ndarray:
        """Float images in [0, 1], (len(rows), H, W, 3), for the batch rows
        ``rows`` of one request (``describe``'s keys)."""
        gc.collect()  # a pass before this one leaves nothing on the device
        latents = self.latent(req, rows)
        gc.collect()  # the denoiser goes before the decoder's temporaries come
        w = sd.load_weights(self._views(req["vae_name"]))
        frames = [decode_frame(self.p, w, self.c["vae"], z) for z in latents]
        imgs = jnp.clip(jnp.concatenate(frames) * 0.5 + 0.5, 0.0, 1.0)
        return np.asarray(jnp.transpose(imgs, (0, 2, 3, 1)), np.float32)
