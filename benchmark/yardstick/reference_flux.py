"""Plain reference of the served FLUX.1-schnell path, from the published
descriptions: black-forest-labs/flux ``src/flux/model.py`` and
``modules/layers.py`` (``Flux``: 2 x 2 patches of the latent as tokens with
the features ordered channel, patch row, patch column; ``img_in`` / ``txt_in``;
``vec`` = timestep embedder (1000·t at 256 frequencies) + pooled-vector
embedder; rotary tables of three axes over the ids (0, row, col) for image
tokens and (0, 0, 0) for text, text first; double-stream blocks — each stream
its own adaLN modulation, fused qkv, per-head RMS norm of q and k, ONE
attention over text ⊕ image, gated projection and gated tanh-GELU MLP; single
-stream blocks on text ⊕ image — one modulation, ``linear1`` to q, k, v and
the MLP's hidden at once, ``linear2`` from attention ⊕ GELU; the last layer),
``modules/conditioner.py`` (``HFEmbedder``: T5 at ``max_length`` 256 padded
with id 0 and handed NO attention mask, its last hidden state; CLIP-L's
pooled output), ``sampling.py`` (``get_schedule(4, shift=False)``: a linear
1 → 0 ladder, Euler steps on the velocity, no classifier-free guidance) and
``modules/autoencoder.py`` (latent / scale + shift into the 16-channel
decoder). ComfyUI's ``simple`` scheduler over ``ModelSamplingDiscreteFlow`` at
schnell's shift 1.0 gives the same ladder.

The arithmetic policy is ``reference_sd``'s, the T5 tower ``reference_t5``'s,
the decoder, the per-head attention and the Euler loop ``reference_mmdit``'s
(imported, not copied): float32 as the six-term sum over bfloat16 pieces,
``bfloat16`` operands with float32 accumulation as the stated precision,
``int8`` operands as the control. It reads only the files the benchmark wrote
and computes nothing with the program.

T5-XXL is 9.5 GB in its file's fp16 and 19 GB in float32: its tensors stay on
the host as views over the file and go to the device one block at a time,
inside the block's own call, so the tower is never whole on the chip in any
type. The denoiser's file (5.1 GB at the cut) goes whole, in its own type.

Departures from the published code: LayerNorm, RMS-norm and softmax
statistics in float32 whatever the mode; attention one head at a time; at
CFG 1.0 the negative prompt conditions nothing and is not encoded.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import reference_sd as sd
from . import reference_t5, safetensors_io, synth
from .reference_mmdit import (_attention, _gelu_tanh, _norm, sample_euler,
                              vae16_decode)
from .reference_sd import F32, PRECISIONS


def _refuse_a_program_that_cannot_load_the_cut() -> None:
    """The harness writes 15 GB and computes the reference (minutes on the
    chip) before it starts the server, so a checkout whose loaders cannot
    serve this configuration — a FLUX file at the depth it has, its tower and
    denoiser resident in 16 bits — would fail only after them, or run out of
    memory. Ask once, at import, and leave at once with a message and a
    non-zero exit code. This is the one thing here that looks at the program."""
    try:
        from comfyui_parallelanything_tpu.models import convert
    except ImportError:
        return  # the benchmark alone (its tests): nothing to ask
    if not (hasattr(convert, "flux_depths") and hasattr(convert, "resident")):
        raise SystemExit(
            "benchmark: this checkout's loaders keep every parameter in float32 "
            "(T5-XXL alone is 19 GB) and read no depth off a FLUX file; the "
            "configuration cannot run here and nothing was run")


_refuse_a_program_that_cannot_load_the_cut()


# -- the denoiser ---------------------------------------------------------------


def rope_tables(ids: np.ndarray, axes_dim, theta: float):
    """``EmbedND``: per axis ``rope(ids[..., i], axes_dim[i], theta)`` —
    angles ids · theta^(−2k / dim) for k < dim / 2 — concatenated along the
    pairs. ids (S, axes) → cos, sin (S, sum(axes_dim) / 2), float32."""
    parts = []
    for i, dim in enumerate(axes_dim):
        omega = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
        parts.append(ids[:, i:i + 1].astype(np.float64) * omega[None])
    ang = np.concatenate(parts, axis=-1)
    return jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)


def token_ids(txt_len: int, hp: int, wp: int) -> np.ndarray:
    """Text ids (0, 0, 0), then the image grid's (0, row, col), row-major."""
    img = np.zeros((hp, wp, 3), np.int64)
    img[..., 1] = np.arange(hp)[:, None]
    img[..., 2] = np.arange(wp)[None, :]
    return np.concatenate([np.zeros((txt_len, 3), np.int64), img.reshape(-1, 3)])


def _rope(x, cos, sin):
    """``apply_rope`` on (B, S, H, D): each adjacent pair (x0, x1) becomes
    (cos·x0 − sin·x1, sin·x0 + cos·x1)."""
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    x0, x1 = pairs[..., 0], pairs[..., 1]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.stack([c * x0 - s * x1, s * x0 + c * x1], axis=-1).reshape(x.shape)


def _rms(x, scale):
    return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + 1e-6) * scale.astype(F32)


def _lin(p, w, key, x):
    return sd._linear(p, x, w[f"{key}.weight"], w.get(f"{key}.bias"))


def _mods(p, w, key, vec, n):
    """``Modulation``: Linear(SiLU(vec)) → ``n`` vectors, each (B, 1, h)."""
    return jnp.split(_lin(p, w, key, sd._silu(vec))[:, None, :], n, axis=-1)


def _qkv(p, heads, w, key, norm_key, x):
    """Fused qkv → (q, k, v), each (B, S, H, D); q and k RMS-normed per head."""
    qkv = _lin(p, w, key, x)
    b, s, _ = qkv.shape
    q, k, v = jnp.moveaxis(qkv.reshape(b, s, 3, heads, -1), 2, 0)
    return (_rms(q, w[f"{norm_key}.query_norm.scale"]),
            _rms(k, w[f"{norm_key}.key_norm.scale"]), v)


def _double_block(p, heads, w, img, txt, vec, cos, sin):
    mods, qkv = {}, {}
    for s, x in (("txt", txt), ("img", img)):
        mods[s] = m = _mods(p, w, f"{s}_mod.lin", vec, 6)
        qkv[s] = _qkv(p, heads, w, f"{s}_attn.qkv", f"{s}_attn.norm",
                      (1.0 + m[1]) * _norm(x) + m[0])
    q, k, v = (jnp.concatenate([qkv["txt"][i], qkv["img"][i]], axis=1) for i in range(3))
    a = _attention(p, _rope(q, cos, sin), _rope(k, cos, sin), v)
    n_txt = txt.shape[1]
    out = []
    for s, x, att in (("img", img, a[:, n_txt:]), ("txt", txt, a[:, :n_txt])):
        m = mods[s]
        x = x + m[2] * _lin(p, w, f"{s}_attn.proj", att)
        h = _lin(p, w, f"{s}_mlp.0", (1.0 + m[4]) * _norm(x) + m[3])
        out.append(x + m[5] * _lin(p, w, f"{s}_mlp.2", _gelu_tanh(h)))
    return out[0], out[1]


def _single_block(p, heads, hidden, w, x, vec, cos, sin):
    shift, scale, gate = _mods(p, w, "modulation.lin", vec, 3)
    h = _lin(p, w, "linear1", (1.0 + scale) * _norm(x) + shift)
    qkv, mlp = h[..., :3 * hidden], h[..., 3 * hidden:]
    b, s, _ = qkv.shape
    q, k, v = jnp.moveaxis(qkv.reshape(b, s, 3, heads, -1), 2, 0)
    q = _rope(_rms(q, w["norm.query_norm.scale"]), cos, sin)
    k = _rope(_rms(k, w["norm.key_norm.scale"]), cos, sin)
    a = _attention(p, q, k, v)
    return x + gate * _lin(p, w, "linear2", jnp.concatenate([a, _gelu_tanh(mlp)], -1))


def _embed(p, w, x, t, context, y):
    """NCHW latent → tokens ``(c ph pw)``-ordered, through ``img_in``; the T5
    states through ``txt_in``; vec = time_in(temb(1000·t)) + vector_in(y)."""
    n, ch, hh, ww = x.shape
    tok = x.reshape(n, ch, hh // 2, 2, ww // 2, 2).transpose(0, 2, 4, 1, 3, 5)
    img = _lin(p, w, "img_in", tok.reshape(n, (hh // 2) * (ww // 2), ch * 4))
    txt = _lin(p, w, "txt_in", context)

    def mlp(key, v):
        return _lin(p, w, f"{key}.out_layer", sd._silu(_lin(p, w, f"{key}.in_layer", v)))

    vec = mlp("time_in", sd.timestep_embedding(1000.0 * t, 256)) + mlp("vector_in", y)
    return img, txt, vec


def _final(p, shape, w, img, vec):
    shift, scale = _mods(p, w, "final_layer.adaLN_modulation.1", vec, 2)
    x = _lin(p, w, "final_layer.linear", (1.0 + scale) * _norm(img) + shift)
    n, ch, hh, ww = shape
    x = x.reshape(n, hh // 2, ww // 2, ch, 2, 2)
    return x.transpose(0, 3, 1, 4, 2, 5).reshape(n, ch, hh, ww)


def flux(p, w, m: dict, x, t, context, y):
    """``Flux.forward`` on NCHW ``x``, flow times ``t`` in [0, 1], T5 states
    ``context`` and pooled vector ``y``, block by block; no guidance embedder
    (schnell)."""
    heads, hidden = m["num_heads"], m["hidden_size"]
    if m.get("guidance_embed"):
        raise NotImplementedError("the reference is schnell's: no guidance embedder")
    cos, sin = rope_tables(token_ids(context.shape[1], x.shape[2] // 2, x.shape[3] // 2),
                           m["axes_dim"], float(m["theta"]))
    embed_keys = ("img_in.", "txt_in.", "time_in.", "vector_in.")
    img, txt, vec = sd._jitted(_embed, p)(
        {k: v for k, v in w.items() if k.startswith(embed_keys)}, x, t, context, y)
    double = sd._jitted(_double_block, p, heads)
    for i in range(m["depth"]):
        img, txt = double(sd._sub(w, f"double_blocks.{i}."), img, txt, vec, cos, sin)
    seq = jnp.concatenate([txt, img], axis=1)
    single = sd._jitted(_single_block, p, heads, hidden)
    for i in range(m["depth_single_blocks"]):
        seq = single(sd._sub(w, f"single_blocks.{i}."), seq, vec, cos, sin)
    return sd._jitted(_final, p, x.shape)(
        {k: v for k, v in w.items() if k.startswith("final_layer.")},
        seq[:, txt.shape[1]:], vec)


# -- schedule ----------------------------------------------------------------------


def schnell_schedule(steps: int) -> np.ndarray:
    """``get_schedule(steps, shift=False)``: ``linspace(1, 0, steps + 1)``.
    ComfyUI's ``simple`` scheduler picks the same out of the thousand-entry
    table sigma(t) = t / 1000 of a flow model at shift 1.0."""
    return np.linspace(1.0, 0.0, steps + 1)


# -- the whole served path ------------------------------------------------------------


def describe(graph: dict) -> dict:
    """What ComfyUI's FLUX.1-schnell graph asks for, read off the graph as
    sent: one KSampler fed by a ``UNETLoader`` directly, an
    ``EmptySD3LatentImage``, two text prompts encoded by the towers of one
    ``DualCLIPLoader`` of type flux, one untiled ``VAEDecode`` on a
    ``VAELoader``'s autoencoder."""
    ks = [(i, n) for i, n in graph.items() if n["class_type"] == "KSampler"]
    if len(ks) != 1:
        raise ValueError("the reference reads graphs with exactly one KSampler")
    ks_id, k = ks[0][0], ks[0][1]["inputs"]

    def node(ref):
        return graph[ref[0]]

    latent, model = node(k["latent_image"]), node(k["model"])
    if latent["class_type"] != "EmptySD3LatentImage" or k.get("denoise", 1.0) != 1.0:
        raise ValueError("the reference reads 16-channel txt2img graphs only")
    if model["class_type"] != "UNETLoader":
        raise ValueError(f"the reference does not know {model['class_type']}")
    dec = [n for n in graph.values() if n["class_type"].startswith("VAEDecode")
           and n["inputs"]["samples"][0] == ks_id]
    if [n["class_type"] for n in dec] != ["VAEDecode"] \
            or node(dec[0]["inputs"]["vae"])["class_type"] != "VAELoader":
        raise ValueError("the reference reads graphs with one untiled VAEDecode "
                         "on a VAELoader's autoencoder")
    texts = [node(k["positive"]), node(k["negative"])]
    loaders = [node(t["inputs"]["clip"]) for t in texts]
    if any(ld["class_type"] != "DualCLIPLoader" or ld["inputs"].get("type") != "flux"
           for ld in loaders):
        raise ValueError("the reference reads prompts encoded through a "
                         "DualCLIPLoader of type flux")
    return {"seed": k["seed"], "steps": k["steps"], "cfg": k["cfg"],
            "sampler_name": k["sampler_name"], "scheduler": k["scheduler"],
            "positive": texts[0]["inputs"]["text"],
            "negative": texts[1]["inputs"]["text"],
            "text_loader": "DualCLIPLoader", **latent["inputs"]}


class Reference:
    """The served path of one configuration in one arithmetic. The denoiser,
    CLIP-L and the decoder go to the device in their files' own types, once a
    part, and are dropped with the object; T5 stays on the host (above)."""

    def __init__(self, config: dict, checkpoint: str, tokenizer, precision: str,
                 tokenizers: dict | None = None, files: dict | None = None):
        if precision not in PRECISIONS:
            raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
        self.c, self.p, self.tok = config, precision, tokenizer
        self.toks = tokenizers or {}
        # (file's path, part) by the group of sizes the part reads
        self._parts = {
            part["sizes"]: ((files or {}).get(spec["file"], checkpoint), part)
            for spec in synth.checkpoint_files(config) for part in spec["parts"]}

    def _views(self, sizes: str) -> dict:
        path, part = self._parts[sizes]
        return safetensors_io.read(path, part["prefix"])

    def _part(self, sizes: str) -> dict:
        return sd.load_weights(self._views(sizes))

    def t5_states(self, ids):
        """The T5 tower's final states for ids (N, S), with or without a mask
        as the configuration says (schnell: without); a method of its own so
        that a test can put a broken tower in its place."""
        t = self.c["text_t5"]
        mask = (np.asarray(ids) != 0) if t.get("attention_mask", True) else None
        return reference_t5.encode(self.p, self._views("text_t5"), t, ids, mask)

    def encode(self, texts: list[str]):
        """→ (context (N, T5's length, context_in_dim), y (N, vec_in_dim)):
        the T5 states, and CLIP-L's final-normed state at the first EOS."""
        c, p = self.c, self.p
        t = dict(c["text"], eos_token_id=c["text"]["vocab_size"] - 1)
        ids = jnp.asarray(np.stack(
            [self.tok.ids(s, t["max_position_embeddings"]) for s in texts]))
        _, _, pooled = sd.clip_hf_text(p, self._part("text"), ids, t)
        ids = np.stack([self.toks["t5"].ids(s) for s in texts])
        return self.t5_states(ids).astype(F32), pooled

    def images(self, req: dict, rows: list[int]) -> np.ndarray:
        """Float images in [0, 1], (len(rows), H, W, 3), for the batch rows
        ``rows`` of one request (``describe``'s keys)."""
        c, p, m = self.c, self.p, self.c["flux"]
        if (req["sampler_name"], req["scheduler"]) != ("euler", "simple") \
                or float(c["schedule"]["shift"]) != 1.0:
            raise NotImplementedError(
                f"reference has no {req['sampler_name']}/{req['scheduler']} at "
                f"shift {c['schedule']['shift']}")
        if float(req["cfg"]) != 1.0:
            raise NotImplementedError("the reference samples without guidance (CFG 1.0)")
        context, y = self.encode([req["positive"]])
        h8, w8 = req["height"] // 8, req["width"] // 8
        ch = c["vae"]["z_channels"]
        # The served path draws the whole batch's noise as one NHWC array
        # from jax.random.key(seed): draw it likewise, keep the sampled rows.
        noise = jax.random.normal(jax.random.key(int(req["seed"]) % 2 ** 63),
                                  (req["batch_size"], h8, w8, ch), F32)
        noise = jnp.transpose(noise[jnp.asarray(rows)], (0, 3, 1, 2))
        w = self._part("flux")

        def velocity(x, sigma):
            return flux(p, w, m, x, jnp.full((1,), sigma, F32), context, y)

        sigmas = schnell_schedule(req["steps"])
        # sigma_max is 1: the flow's start is the noise itself. One row at a
        # time, as reference_sd does.
        latents = [sample_euler(velocity, noise[k:k + 1] * float(sigmas[0]),
                                sigmas).block_until_ready() for k in range(len(rows))]
        del w
        w = self._part("vae")
        imgs = [jnp.clip(vae16_decode(p, w, c["vae"], z) * 0.5 + 0.5, 0.0, 1.0)
                for z in latents]
        return np.asarray(jnp.transpose(jnp.concatenate(imgs), (0, 2, 3, 1)), np.float32)
