"""Plain reference of the served Z-Image-Turbo path, from the published
descriptions: Tongyi-MAI/Z-Image ``src/zimage/transformer.py`` and
``pipeline.py`` (diffusers ``ZImageTransformer2DModel`` / ``ZImagePipeline``),
Qwen/Qwen3-4B's ``config.json`` with HF ``modeling_qwen3.py``, and BFL's
``modules/autoencoder.py`` for the 16-channel decoder.

The denoiser (``zimage``): the latent's 2 x 2 patches as tokens, features
ordered patch row, patch column, channel, through ``x_embedder``; the text
tower's states through ``cap_embedder`` (RMS norm, linear); each stream padded
to the next multiple of 32 tokens with its learned pad token (the pad tokens
take part in every attention); ``t_emb`` = Linear(SiLU(Linear(sincos_256(t ·
t_scale)))) with t = 1 − σ; rotary tables of three axes (θ 256, dims 32 / 48 /
48, complex pairs interleaved) over position ids (1 + i, 0, 0) for caption
token i and (L_cap_padded + 1, h, w) for the image patch (h, w), (0, 0, 0) for
an image pad token; ``noise_refiner`` blocks on the image tokens,
``context_refiner`` blocks (no modulation) on the caption tokens, ``layers``
on image ⊕ caption; a block is
``x += tanh(gate_a) · RMS(Attn(RMS(x) · (1 + scale_a)))``,
``x += tanh(gate_m) · RMS(W2(SiLU(W1 h) · W3 h))``, ``h = RMS(x) · (1 +
scale_m)``, the four vectors one linear layer of ``t_emb`` (no SiLU before
it), q and k RMS-normed per head; the final layer ``Linear(LayerNorm(x_img) ·
(1 + Linear(SiLU(t_emb))))``. The pipeline hands the scheduler the NEGATED
model output as the velocity.

The tower (``qwen3_states``): token embedding, then per layer
``h += O(Attn_causal(rope(qnorm(Q x̂)), rope(knorm(K x̂)), V x̂))``, x̂ =
RMS(h), four query heads a key/value head, rotary over the position in the
half-split convention (pair k with k + 64), θ 1e6; ``h += Down(SiLU(Gate x̂')
· Up x̂')``. The pipeline takes ``hidden_states[-2]`` — the stream BEFORE the
last layer, un-normed — of the chat-templated prompt and keeps the valid
tokens only; the reference runs the valid tokens alone.

Sampling: ComfyUI's ``simple`` scheduler over the flow table at shift 3.0
(``ModelSamplingAuraFlow``), Euler, no classifier-free guidance: 8 forwards.
Tongyi's own pipeline (``num_inference_steps`` 9, "8 DiT forwards") walks the
same kind of ladder.

The arithmetic policy is ``reference_sd``'s; the decoder, the per-head
attention and the Euler loop are ``reference_mmdit``'s (imported, not copied):
float32 as the six-term sum over bfloat16 pieces, ``bfloat16`` operands with
float32 accumulation as the stated precision, ``int8`` operands as the
control. It reads only the files the benchmark wrote and computes nothing
with the program.

Qwen3-4B is 8 GB in its file's bfloat16 and 16 GB in float32: its tensors stay
on the host as views over the file and go to the device one layer at a time,
inside the layer's own call, so the tower is never whole on the chip in any
type. The denoiser's file (4.35 GB at the cut) goes whole, in its own type.

Departures from the published code: RMS-norm, LayerNorm and softmax
statistics in float32 whatever the mode; attention one head at a time; at
CFG 1.0 the negative prompt conditions nothing and is not encoded.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import reference_sd as sd
from . import safetensors_io, synth
from .layout_zimage import head_dim
from .reference_mmdit import _attention, _norm, flow_sigma, sample_euler, vae16_decode
from .reference_sd import F32, PRECISIONS

SEQ_MULTI_OF = 32  # transformer.py's constant


def _refuse_a_program_that_cannot_serve_it() -> None:
    """The harness writes 12.6 GB and computes the reference (minutes on the
    chip) before it starts the server, so a checkout whose program has no
    Z-Image family — the parent of the PR that brought it — would fail only
    after them. Ask once, at import, and leave at once with a message and a
    non-zero exit code. This is the one thing here that looks at the program."""
    try:
        from comfyui_parallelanything_tpu import models
    except ImportError:
        return  # the benchmark alone (its tests): nothing to ask
    if not (hasattr(models, "load_zimage_checkpoint")
            and hasattr(models, "load_qwen3_checkpoint")):
        raise SystemExit(
            "benchmark: this checkout's program has no Z-Image denoiser and no "
            "Qwen3 text tower (models.load_zimage_checkpoint / "
            "load_qwen3_checkpoint); the configuration cannot run here and "
            "nothing was run")


_refuse_a_program_that_cannot_serve_it()


# -- shared pieces -----------------------------------------------------------------


def _rms(x, scale, eps):
    return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale.astype(F32)


def _lin(p, w, key, x):
    return sd._linear(p, x, w[f"{key}.weight"], w.get(f"{key}.bias"))


def padded(n: int) -> int:
    return -(-n // SEQ_MULTI_OF) * SEQ_MULTI_OF


# -- the text tower ----------------------------------------------------------------


def _rotate_half(x, cos, sin):
    """HF ``apply_rotary_pos_emb``: x · cos + rotate_half(x) · sin with the
    angles repeated over both halves, on (B, S, H, D); cos, sin (S, D / 2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _qwen3_layer(p, heads, kv_heads, eps, w, h, cos, sin):
    b, s, _ = h.shape
    x = _rms(h, w["input_layernorm.weight"], eps)
    q = _lin(p, w, "self_attn.q_proj", x).reshape(b, s, heads, -1)
    k = _lin(p, w, "self_attn.k_proj", x).reshape(b, s, kv_heads, -1)
    v = _lin(p, w, "self_attn.v_proj", x).reshape(b, s, kv_heads, -1)
    q = _rotate_half(_rms(q, w["self_attn.q_norm.weight"], eps), cos, sin)
    k = _rotate_half(_rms(k, w["self_attn.k_norm.weight"], eps), cos, sin)
    d, group = q.shape[-1], heads // kv_heads
    # Query head j reads key/value head j // group; a query sees the keys at
    # or before its own position.
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


def qwen3_states(p, w, c: dict, ids, upto: int | None = None):
    """The residual stream (B, S, hidden) after ``upto`` layers (all but the
    last unless given: HF's ``hidden_states[-2]``), un-normed, for token ids
    (B, S) with no padding; ``w`` the file's tensors (host views are fine: a
    layer's go to the device inside its call), ``c`` its sizes."""
    eps = float(c.get("rms_norm_eps", 1e-6))
    n = c["num_hidden_layers"] - 1 if upto is None else upto
    d, s = c["head_dim"], np.asarray(ids).shape[1]
    omega = 1.0 / float(c["rope_theta"]) ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(s, dtype=np.float64)[:, None] * omega[None]
    cos, sin = jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)
    h = jnp.asarray(np.asarray(w["model.embed_tokens.weight"][np.asarray(ids)]), F32)
    layer = sd._jitted(_qwen3_layer, p, c["num_attention_heads"],
                       c["num_key_value_heads"], eps)
    for i in range(n):
        h = layer(sd._sub(w, f"model.layers.{i}."), h, cos, sin)
    return h


# -- the denoiser ------------------------------------------------------------------


def rope_tables(ids: np.ndarray, axes_dims, theta: float):
    """``RopeEmbedder``: per axis the angles ids · theta^(−2k / dim) for
    k < dim / 2, concatenated along the pairs. ids (S, axes) → cos, sin
    (S, sum(axes_dims) / 2), float32."""
    parts = []
    for i, dim in enumerate(axes_dims):
        omega = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
        parts.append(ids[:, i:i + 1].astype(np.float64) * omega[None])
    ang = np.concatenate(parts, axis=-1)
    return jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)


def position_ids(cap_len: int, hp: int, wp: int) -> tuple[np.ndarray, np.ndarray]:
    """(image ids, caption ids), each padded to its multiple of 32: caption
    token i at (1 + i, 0, 0) through its padding; the image patch (h, w) at
    (padded caption length + 1, h, w), row-major, its pad tokens at
    (0, 0, 0)."""
    cap_pad = padded(cap_len)
    cap = np.zeros((cap_pad, 3), np.int64)
    cap[:, 0] = 1 + np.arange(cap_pad)
    img = np.zeros((padded(hp * wp), 3), np.int64)
    grid = np.zeros((hp, wp, 3), np.int64)
    grid[..., 0] = cap_pad + 1
    grid[..., 1] = np.arange(hp)[:, None]
    grid[..., 2] = np.arange(wp)[None, :]
    img[: hp * wp] = grid.reshape(-1, 3)
    return img, cap


def _rope(x, cos, sin):
    """``apply_rotary_emb`` on (B, S, H, D): adjacent pairs (x0, x1) as
    complex numbers times cos + i·sin."""
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    x0, x1 = pairs[..., 0], pairs[..., 1]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.stack([c * x0 - s * x1, s * x0 + c * x1], axis=-1).reshape(x.shape)


def _block(p, heads, eps, modulated, w, x, cos, sin, temb):
    if modulated:
        mod = _lin(p, w, "adaLN_modulation.0", temb)[:, None, :]
        scale_a, gate_a, scale_m, gate_m = jnp.split(mod, 4, axis=-1)
        scale_a, scale_m = 1.0 + scale_a, 1.0 + scale_m
        gate_a, gate_m = jnp.tanh(gate_a), jnp.tanh(gate_m)
    else:
        scale_a = scale_m = gate_a = gate_m = 1.0
    b, s, _ = x.shape
    h = _rms(x, w["attention_norm1.weight"], eps) * scale_a
    q, k, v = (_lin(p, w, f"attention.{n}", h).reshape(b, s, heads, -1)
               for n in ("to_q", "to_k", "to_v"))
    q = _rope(_rms(q, w["attention.norm_q.weight"], eps), cos, sin)
    k = _rope(_rms(k, w["attention.norm_k.weight"], eps), cos, sin)
    a = _lin(p, w, "attention.to_out.0", _attention(p, q, k, v))
    x = x + gate_a * _rms(a, w["attention_norm2.weight"], eps)
    h = _rms(x, w["ffn_norm1.weight"], eps) * scale_m
    f = _lin(p, w, "feed_forward.w2",
             sd._silu(_lin(p, w, "feed_forward.w1", h)) * _lin(p, w, "feed_forward.w3", h))
    return x + gate_m * _rms(f, w["ffn_norm2.weight"], eps)


def _embed(p, key, eps, t_scale, img_pad, cap_pad, w, x, t, cap):
    """NCHW latent → tokens (ph pw c)-ordered through the patch embedder, the
    caption through its embedder, both padded with their learned tokens;
    ``t_emb`` from the model's time t (1 at the image, 0 at noise)."""
    n, ch, hh, ww = x.shape
    tok = x.reshape(n, ch, hh // 2, 2, ww // 2, 2).transpose(0, 2, 4, 3, 5, 1)
    img = _lin(p, w, f"all_x_embedder.{key}", tok.reshape(n, (hh // 2) * (ww // 2), ch * 4))
    img = jnp.concatenate([img, jnp.broadcast_to(
        w["x_pad_token"].astype(F32)[None], (n, img_pad, img.shape[-1]))], axis=1)
    cap = _lin(p, w, "cap_embedder.1", _rms(cap, w["cap_embedder.0.weight"], eps))
    cap = jnp.concatenate([cap, jnp.broadcast_to(
        w["cap_pad_token"].astype(F32)[None], (n, cap_pad, cap.shape[-1]))], axis=1)
    temb = _lin(p, w, "t_embedder.mlp.0", sd.timestep_embedding(t * t_scale, 256))
    return img, cap, _lin(p, w, "t_embedder.mlp.2", sd._silu(temb))


def _final(p, key, shape, w, img, temb):
    scale = 1.0 + _lin(p, w, f"all_final_layer.{key}.adaLN_modulation.1",
                       sd._silu(temb))[:, None, :]
    x = _lin(p, w, f"all_final_layer.{key}.linear", _norm(img) * scale)
    n, ch, hh, ww = shape
    x = x[:, : (hh // 2) * (ww // 2)].reshape(n, hh // 2, ww // 2, 2, 2, ch)
    return x.transpose(0, 5, 1, 3, 2, 4).reshape(n, ch, hh, ww)


def zimage(p, w, m: dict, x, t, cap):
    """``ZImageTransformer2DModel.forward`` on NCHW ``x``, the model's times
    ``t`` in [0, 1] (1 − σ) and the tower's states ``cap`` (B, L, cap_feat_dim)
    of the valid tokens, block by block. Its output as published: the
    pipeline negates it."""
    heads, eps = m["n_heads"], float(m["norm_eps"])
    if head_dim(m) != sum(m["axes_dims"]):
        raise ValueError("the rotary axes do not fill the head")
    key = f"{m['all_patch_size'][0]}-{m['all_f_patch_size'][0]}"
    hp, wp, n_cap = x.shape[2] // 2, x.shape[3] // 2, cap.shape[1]
    img_ids, cap_ids = position_ids(n_cap, hp, wp)
    theta = float(m["rope_theta"])
    tables = {k: rope_tables(ids, m["axes_dims"], theta) for k, ids in
              (("img", img_ids), ("cap", cap_ids),
               ("all", np.concatenate([img_ids, cap_ids])))}
    embed_keys = ("all_x_embedder.", "cap_embedder.", "t_embedder.",
                  "x_pad_token", "cap_pad_token")
    img, cap, temb = sd._jitted(
        _embed, p, key, eps, float(m["t_scale"]), len(img_ids) - hp * wp,
        len(cap_ids) - n_cap)(
        {k: v for k, v in w.items() if k.startswith(embed_keys)}, x, t, cap)
    modulated = sd._jitted(_block, p, heads, eps, True)
    plain = sd._jitted(_block, p, heads, eps, False)
    for i in range(m["n_refiner_layers"]):
        img = modulated(sd._sub(w, f"noise_refiner.{i}."), img, *tables["img"], temb)
    for i in range(m["n_refiner_layers"]):
        cap = plain(sd._sub(w, f"context_refiner.{i}."), cap, *tables["cap"], temb)
    seq = jnp.concatenate([img, cap], axis=1)  # image first, then the caption
    for i in range(m["n_layers"]):
        seq = modulated(sd._sub(w, f"layers.{i}."), seq, *tables["all"], temb)
    return sd._jitted(_final, p, key, x.shape)(
        {k: v for k, v in w.items() if k.startswith("all_final_layer.")},
        seq[:, : len(img_ids)], temb)


# -- schedule ----------------------------------------------------------------------


def simple_sigmas(steps: int, shift: float, timesteps: int = 1000) -> np.ndarray:
    """ComfyUI's ``simple`` scheduler over ``ModelSamplingDiscreteFlow``'s
    table sigma(t) = shift·t / (1 + (shift − 1)·t) at t = 1/1000 … 1: every
    (1000 / steps)-th entry from the top, then 0."""
    table = flow_sigma(np.arange(1, timesteps + 1, dtype=np.float64) / timesteps, shift)
    stride = len(table) / steps
    return np.asarray([table[-(1 + int(k * stride))] for k in range(steps)] + [0.0])


# -- the whole served path ------------------------------------------------------------


def describe(graph: dict) -> dict:
    """What ComfyUI's Z-Image-Turbo graph asks for, read off the graph as
    sent: one KSampler whose model is a ``UNETLoader``'s behind one
    ``ModelSamplingAuraFlow`` (its shift), an ``EmptySD3LatentImage``, two
    text prompts encoded by the tower of one ``CLIPLoader`` of type lumina2,
    one untiled ``VAEDecode`` on a ``VAELoader``'s autoencoder."""
    ks = [(i, n) for i, n in graph.items() if n["class_type"] == "KSampler"]
    if len(ks) != 1:
        raise ValueError("the reference reads graphs with exactly one KSampler")
    ks_id, k = ks[0][0], ks[0][1]["inputs"]

    def node(ref):
        return graph[ref[0]]

    latent, patch = node(k["latent_image"]), node(k["model"])
    if latent["class_type"] != "EmptySD3LatentImage" or k.get("denoise", 1.0) != 1.0:
        raise ValueError("the reference reads 16-channel txt2img graphs only")
    if patch["class_type"] != "ModelSamplingAuraFlow" \
            or node(patch["inputs"]["model"])["class_type"] != "UNETLoader":
        raise ValueError("the reference reads a UNETLoader's model behind one "
                         f"ModelSamplingAuraFlow, not {patch['class_type']}")
    dec = [n for n in graph.values() if n["class_type"].startswith("VAEDecode")
           and n["inputs"]["samples"][0] == ks_id]
    if [n["class_type"] for n in dec] != ["VAEDecode"] \
            or node(dec[0]["inputs"]["vae"])["class_type"] != "VAELoader":
        raise ValueError("the reference reads graphs with one untiled VAEDecode "
                         "on a VAELoader's autoencoder")
    texts = [node(k["positive"]), node(k["negative"])]
    loaders = [node(t["inputs"]["clip"]) for t in texts]
    if any(t["class_type"] != "CLIPTextEncode" for t in texts) or any(
            ld["class_type"] != "CLIPLoader" or ld["inputs"].get("type") != "lumina2"
            for ld in loaders):
        raise ValueError("the reference reads prompts encoded by CLIPTextEncode "
                         "through a CLIPLoader of type lumina2")
    return {"seed": k["seed"], "steps": k["steps"], "cfg": k["cfg"],
            "sampler_name": k["sampler_name"], "scheduler": k["scheduler"],
            "shift": float(patch["inputs"]["shift"]),
            "positive": texts[0]["inputs"]["text"],
            "negative": texts[1]["inputs"]["text"],
            "text_loader": "CLIPLoader", **latent["inputs"]}


class Reference:
    """The served path of one configuration in one arithmetic. The denoiser
    and the decoder go to the device in their files' own types, once a part,
    and are dropped with the object; the tower stays on the host (above).
    ``tokenizer`` (CLIP's, which the harness always writes) is not used: the
    prompt goes through ``tokenizers["qwen"]``."""

    def __init__(self, config: dict, checkpoint: str, tokenizer, precision: str,
                 tokenizers: dict | None = None, files: dict | None = None):
        if precision not in PRECISIONS:
            raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
        self.c, self.p = config, precision
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

    def tower_states(self, ids):
        """The tower's state before its last layer for ids (N, S) with no
        padding; a method of its own so that a test can put a broken tower in
        its place."""
        return qwen3_states(self.p, self._views("text"), self.c["text"], ids)

    def encode(self, text: str):
        """→ (1, valid tokens, cap_feat_dim): the templated prompt's states."""
        ids = self.toks["qwen"].ids(text)[None]
        return self.tower_states(ids).astype(F32)

    def images(self, req: dict, rows: list[int]) -> np.ndarray:
        """Float images in [0, 1], (len(rows), H, W, 3), for the batch rows
        ``rows`` of one request (``describe``'s keys)."""
        c, p, m = self.c, self.p, self.c["zimage"]
        if (req["sampler_name"], req["scheduler"]) != ("euler", "simple"):
            raise NotImplementedError(
                f"reference has no {req['sampler_name']}/{req['scheduler']}")
        if float(req["cfg"]) != 1.0:
            raise NotImplementedError("the reference samples without guidance (CFG 1.0)")
        cap = self.encode(req["positive"])
        h8, w8 = req["height"] // 8, req["width"] // 8
        ch = c["vae"]["z_channels"]
        # The served path draws the whole batch's noise as one NHWC array
        # from jax.random.key(seed): draw it likewise, keep the sampled rows.
        noise = jax.random.normal(jax.random.key(int(req["seed"]) % 2 ** 63),
                                  (req["batch_size"], h8, w8, ch), F32)
        noise = jnp.transpose(noise[jnp.asarray(rows)], (0, 3, 1, 2))
        w = self._part("zimage")

        def velocity(x, sigma):
            # the pipeline's timestep (1000 − t) / 1000 and its sign
            return -zimage(p, w, m, x, jnp.full((1,), 1.0 - sigma, F32), cap)

        sigmas = simple_sigmas(req["steps"], req["shift"])
        # sigma_max is 1: the flow's start is the noise itself. One row at a
        # time, as reference_sd does.
        latents = [sample_euler(velocity, noise[k:k + 1] * float(sigmas[0]),
                                sigmas).block_until_ready() for k in range(len(rows))]
        del w
        w = self._part("vae")
        imgs = [jnp.clip(vae16_decode(p, w, c["vae"], z) * 0.5 + 0.5, 0.0, 1.0)
                for z in latents]
        return np.asarray(jnp.transpose(jnp.concatenate(imgs), (0, 2, 3, 1)), np.float32)
