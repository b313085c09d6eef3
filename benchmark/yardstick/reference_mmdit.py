"""Plain reference of the served Stable Diffusion 3.5 path, from the published
descriptions: Stability-AI/sd3.5 ``mmditx.py`` (the MMDiT-X: patch embedding
with the cropped position table, timestep and pooled-vector embedders, joint
blocks with adaLN-Zero modulation of both streams, q/k RMS norm per head, joint
attention over text and image tokens, a second attention over the image tokens
alone in the ``dual_attention_layers``, the last block's text stream pre-only,
final layer, unpatchify), ``other_impls.py`` (CLIP-L and bigG at their
penultimate layers without the final norm, pooled at the first EOS), ComfyUI's
``ModelSamplingDiscreteFlow`` (sigma = shift·t / (1 + (shift − 1)·t), timestep
= 1000·sigma), its ``sgm_uniform`` spacing, Euler steps on the flow with
classifier-free guidance, and the 16-channel kl-f8 decoder without quant
convolutions (latent / scale + shift).

The arithmetic policy, the two text towers and the decoder's blocks are
``reference_sd``'s (imported, not copied): float32 as the six-term sum over
bfloat16 pieces, ``bfloat16`` operands with float32 accumulation as the stated
precision, ``int8`` operands as the control. Like it, this file reads only the
checkpoint the benchmark wrote and computes nothing with the program.

Departures from the published code: LayerNorm and RMS-norm statistics in
float32 whatever the mode; attention one head at a time (the same sums; the
4173² logits of 24 heads at once do not fit beside the weights); without the
T5-XXL tower the context is the 77 CLIP tokens, as ComfyUI conditions
(``sd3_clip.py``: ``out = lg_out``) — Stability's ``sd3_infer.py`` appends 77
zero rows in T5's place instead, which this graph's host does not.

A configuration with a ``text_t5`` block (and a ``TripleCLIPLoader`` in its
graph) has the third tower: the context is then the CLIP-L ⊕ bigG stream
zero-padded to ``joint_attention_dim`` (2048 → 4096 at published widths),
concatenated ALONG THE TOKENS with the T5 encoder's final states
(``reference_t5.py``; ``sd3_clip.py``: ``torch.cat([lg_out, t5_out], dim=-2)``),
the T5 ids from the configuration's named tokenizer ``t5`` at its
``max_length`` (77: ComfyUI's SD3 T5 tokenizer pads to at least 77 and does
not truncate; a prompt of the traffic has far fewer pieces). The towers then
come from files of their own (``checkpoint.files``), each read in the layout
its part names.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import reference_sd as sd
from . import reference_t5, synth
from .layout_mmdit import dual_layers
from .reference_sd import F32, PRECISIONS

# The two towers of the bundle, as the configuration's checkpoint parts spell
# them; a program whose stock loader does not read them cannot serve the graph.
BUNDLED_TOWERS = ("text_encoders.clip_l.transformer.",
                  "text_encoders.clip_g.transformer.")


def _refuse_a_program_without_the_bundle_loader() -> None:
    """The harness computes the reference (minutes on the chip) before it
    starts the server, so a checkout whose ``CheckpointLoaderSimple`` answers
    this family with "does not bundle text encoders" would fail only after
    them. Ask once, at import, and leave at once with a message and a non-zero
    exit code. This is the one thing here that looks at the program."""
    try:
        from comfyui_parallelanything_tpu import nodes_compat
    except ImportError:
        return  # the benchmark alone (its tests): nothing to ask
    if not set(BUNDLED_TOWERS) <= set(getattr(nodes_compat, "SD3_BUNDLED_TOWERS", ())):
        raise SystemExit(
            "benchmark: this checkout's CheckpointLoaderSimple does not read the "
            "text towers an SD3-family *_incl_clips file bundles "
            f"({', '.join(BUNDLED_TOWERS)}); the configuration cannot run here "
            "and nothing was run")


_refuse_a_program_without_the_bundle_loader()


# -- the denoiser ---------------------------------------------------------------


def _norm(x, eps=1e-6):
    """LayerNorm without affine parameters (``elementwise_affine=False``)."""
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps)


def _modulate(x, shift, scale):
    return x * (1.0 + scale[:, None]) + shift[:, None]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _attention(p, q, k, v):
    """(B, S, H, D) → (B, S, H·D): softmax(q kᵀ / √D) v, one head at a time;
    the two products on bfloat16 operands under every mode below float32."""
    p = "float32" if p == "float32" else "bfloat16"
    b, s, h, d = q.shape

    def head(qkv):
        qh, kh, vh = qkv
        logits = sd._ein(p, "bqd,bkd->bqk", qh, kh) * (d ** -0.5)
        return sd._ein(p, "bqk,bkd->bqd", jax.nn.softmax(logits, axis=-1), vh)

    out = lax.map(head, tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v)))
    return jnp.moveaxis(out, 0, 2).reshape(b, s, h * d)


def _pre_attention(p, heads, w, key, x):
    """``SelfAttention.pre_attention``: qkv, split by heads, RMS norm of q and
    k over the head dim where the checkpoint carries its weights."""
    qkv = sd._linear(p, x, w[f"{key}.qkv.weight"], w[f"{key}.qkv.bias"])
    b, s, _ = qkv.shape
    q, k, v = jnp.moveaxis(qkv.reshape(b, s, 3, heads, -1), 2, 0)
    if f"{key}.ln_q.weight" in w:
        def rms(t, g):
            return t * lax.rsqrt((t * t).mean(-1, keepdims=True) + 1e-6) * g.astype(F32)

        q, k = rms(q, w[f"{key}.ln_q.weight"]), rms(k, w[f"{key}.ln_k.weight"])
    return q, k, v


def _mods(p, w, key, c, n):
    out = sd._linear(p, sd._silu(c), w[f"{key}.adaLN_modulation.1.weight"],
                     w[f"{key}.adaLN_modulation.1.bias"])
    return jnp.split(out, n, axis=-1)


def _mlp(p, w, key, x):
    x = sd._linear(p, x, w[f"{key}.mlp.fc1.weight"], w[f"{key}.mlp.fc1.bias"])
    return sd._linear(p, _gelu_tanh(x), w[f"{key}.mlp.fc2.weight"], w[f"{key}.mlp.fc2.bias"])


def _proj(p, w, key, a):
    return sd._linear(p, a, w[f"{key}.proj.weight"], w[f"{key}.proj.bias"])


def _joint_block(p, heads, dual, pre_only, w, x, ctx, c):
    """One ``JointBlock`` (``block_mixing``): both streams modulate and
    project separately, attend jointly over text ⊕ image tokens, then each
    goes through its own projection and MLP. ``dual``: the x block's nine
    modulation vectors and second attention over the image tokens alone
    (MMDiT-X). ``pre_only``: the last block's text stream feeds the joint
    attention and ends there."""
    cb, xb = "context_block", "x_block"
    cm = _mods(p, w, cb, c, 2 if pre_only else 6)
    xm = _mods(p, w, xb, c, 9 if dual else 6)
    cq, ck, cv = _pre_attention(p, heads, w, f"{cb}.attn",
                                _modulate(_norm(ctx), cm[0], cm[1]))
    xn = _norm(x)
    xq, xk, xv = _pre_attention(p, heads, w, f"{xb}.attn", _modulate(xn, xm[0], xm[1]))
    a = _attention(p, *(jnp.concatenate(t, axis=1)
                        for t in ((cq, xq), (ck, xk), (cv, xv))))
    n_ctx = ctx.shape[1]
    ca, xa = a[:, :n_ctx], a[:, n_ctx:]
    x = x + xm[2][:, None] * _proj(p, w, f"{xb}.attn", xa)
    if dual:
        q2, k2, v2 = _pre_attention(p, heads, w, f"{xb}.attn2",
                                    _modulate(xn, xm[6], xm[7]))
        x = x + xm[8][:, None] * _proj(p, w, f"{xb}.attn2", _attention(p, q2, k2, v2))
    x = x + xm[5][:, None] * _mlp(p, w, xb, _modulate(_norm(x), xm[3], xm[4]))
    if pre_only:
        return x, ctx
    ctx = ctx + cm[2][:, None] * _proj(p, w, f"{cb}.attn", ca)
    ctx = ctx + cm[5][:, None] * _mlp(p, w, cb, _modulate(_norm(ctx), cm[3], cm[4]))
    return x, ctx


def _embed(p, patch, pos_max, freq_dim, w, x, t, context, y):
    """NCHW latent → tokens plus the cropped position table; the text tokens
    through ``context_embedder``; c = t_embedder(t) + y_embedder(y)."""
    n, ch, hh, ww = x.shape
    hp, wp = hh // patch, ww // patch
    tok = x.reshape(n, ch, hp, patch, wp, patch).transpose(0, 2, 4, 1, 3, 5)
    kernel = w["x_embedder.proj.weight"]
    tok = sd._linear(p, tok.reshape(n, hp * wp, -1), kernel.reshape(kernel.shape[0], -1),
                     w["x_embedder.proj.bias"])
    top, left = (pos_max - hp) // 2, (pos_max - wp) // 2
    pos = w["pos_embed"].astype(F32).reshape(pos_max, pos_max, -1)
    tok = tok + pos[top:top + hp, left:left + wp].reshape(1, hp * wp, -1)
    ctx = sd._linear(p, context, w["context_embedder.weight"], w["context_embedder.bias"])

    def mlp(key, v):
        v = sd._linear(p, v, w[f"{key}.mlp.0.weight"], w[f"{key}.mlp.0.bias"])
        return sd._linear(p, sd._silu(v), w[f"{key}.mlp.2.weight"], w[f"{key}.mlp.2.bias"])

    c = mlp("t_embedder", sd.timestep_embedding(t, freq_dim)) + mlp("y_embedder", y)
    return tok, ctx, c


def _final(p, patch, out_ch, shape, w, x, c):
    shift, scale = _mods(p, w, "final_layer", c, 2)
    x = sd._linear(p, _modulate(_norm(x), shift, scale),
                   w["final_layer.linear.weight"], w["final_layer.linear.bias"])
    n, _, hh, ww = shape
    hp, wp = hh // patch, ww // patch
    x = x.reshape(n, hp, wp, patch, patch, out_ch)
    return jnp.einsum("nhwpqc->nchpwq", x).reshape(n, out_ch, hh, ww)


def mmdit(p, w, m: dict, x, t, context, y):
    """``MMDiTX.forward`` on NCHW ``x``, timesteps ``t`` (1000·sigma), text
    tokens ``context`` and pooled vector ``y``, block by block."""
    heads, n = m["num_attention_heads"], m["num_layers"]
    embed_keys = ("x_embedder.", "pos_embed", "context_embedder.", "t_embedder.",
                  "y_embedder.")
    tok, ctx, c = sd._jitted(_embed, p, m["patch_size"], m["pos_embed_max_size"],
                             m["frequency_embedding_size"])(
        {k: v for k, v in w.items() if k.startswith(embed_keys)}, x, t, context, y)
    for i in range(n):
        block = sd._jitted(_joint_block, p, heads, i in dual_layers(m), i == n - 1)
        tok, ctx = block(sd._sub(w, f"joint_blocks.{i}."), tok, ctx, c)
    return sd._jitted(_final, p, m["patch_size"], m["out_channels"], x.shape)(
        {k: v for k, v in w.items() if k.startswith("final_layer.")}, tok, c)


# -- the decoder -----------------------------------------------------------------


def _vae16_in(p, scale, shift, w, z):
    return sd._conv(p, z / scale + shift, w["decoder.conv_in.weight"],
                    w["decoder.conv_in.bias"])


def vae16_decode(p, w, v: dict, z):
    """Scaled latent (NCHW, 16 channels) → decoder output in [-1, 1]: the
    kl-f8 decoder of ``reference_sd`` entered at ``latent / scale + shift``,
    with no ``post_quant_conv``."""
    res = sd._jitted(sd._vae_res, p)
    h = sd._jitted(_vae16_in, p, float(v["scale_factor"]), float(v["shift_factor"]))(
        {k: w[k] for k in w if k.startswith("decoder.conv_in.")}, z)
    h = res(sd._sub(w, "decoder.mid.block_1."), h)
    h = sd._jitted(sd._vae_attn, p)(sd._sub(w, "decoder.mid.attn_1."), h)
    h = res(sd._sub(w, "decoder.mid.block_2."), h)
    for lvl in reversed(range(len(v["ch_mult"]))):
        for i in range(v["num_res_blocks"] + 1):
            h = res(sd._sub(w, f"decoder.up.{lvl}.block.{i}."), h)
        if lvl != 0:
            h = sd._jitted(sd._plain_conv, p, 1, True)(
                sd._sub(w, f"decoder.up.{lvl}.upsample.conv."), h)
    return sd._jitted(sd._vae_out, p)(
        {k: w[k] for k in w if k.startswith(("decoder.norm_out.", "decoder.conv_out."))}, h)


# -- schedule and sampler ----------------------------------------------------------


def flow_sigma(t, shift: float):
    """ComfyUI ``time_snr_shift``: shift·t / (1 + (shift − 1)·t)."""
    return shift * t / (1.0 + (shift - 1.0) * t)


def sgm_uniform_sigmas(steps: int, shift: float, timesteps: int = 1000) -> np.ndarray:
    """ComfyUI ``normal_scheduler(sgm=True)`` over ``ModelSamplingDiscreteFlow``:
    ``steps + 1`` timesteps evenly from ``timestep(sigma_max)`` to
    ``timestep(sigma_min)`` (timestep = 1000·sigma, sigma_min the table's first
    entry), the last dropped, each through ``sigma()``; then 0."""
    sigma_min = flow_sigma(1.0 / timesteps, shift)
    ts = np.linspace(float(timesteps), sigma_min * timesteps, steps + 1)[:-1]
    return np.append(flow_sigma(ts / timesteps, shift), 0.0)


def sample_euler(velocity, x, sigmas):
    """k-diffusion ``sample_euler`` on a CONST model: denoised = x − sigma·v,
    d = (x − denoised) / sigma = v, x += d·(sigma_next − sigma)."""
    for s, s_next in zip(sigmas[:-1], sigmas[1:]):
        x = x + velocity(x, float(s)) * float(s_next - s)
    return x


# -- the whole served path ------------------------------------------------------------


def describe(graph: dict) -> dict:
    """What ComfyUI's SD3.5 txt2img graph asks for, read off the graph as
    sent: one KSampler fed by the checkpoint loader directly (the family's
    shift, no ModelSamplingSD3 node), an EmptySD3LatentImage, two text
    prompts encoded by the towers of one loader (``text_loader``: the
    checkpoint's bundled pair, or a ``TripleCLIPLoader``'s three files), one
    untiled VAEDecode."""
    ks = [(i, n) for i, n in graph.items() if n["class_type"] == "KSampler"]
    if len(ks) != 1:
        raise ValueError("the reference reads graphs with exactly one KSampler")
    ks_id, k = ks[0][0], ks[0][1]["inputs"]

    def node(ref):
        return graph[ref[0]]

    latent, model = node(k["latent_image"]), node(k["model"])
    if latent["class_type"] != "EmptySD3LatentImage" or k.get("denoise", 1.0) != 1.0:
        raise ValueError("the reference reads SD3 txt2img graphs only")
    if model["class_type"] != "CheckpointLoaderSimple":
        raise ValueError(f"the reference does not know {model['class_type']}")
    dec = [n["class_type"] for n in graph.values()
           if n["class_type"].startswith("VAEDecode") and n["inputs"]["samples"][0] == ks_id]
    if dec != ["VAEDecode"]:
        raise ValueError("the reference reads graphs with one untiled VAEDecode")
    texts = [node(k["positive"]), node(k["negative"])]
    loaders = {node(t["inputs"]["clip"])["class_type"] for t in texts}
    if len(loaders) != 1 or not loaders <= {"CheckpointLoaderSimple", "TripleCLIPLoader"}:
        raise ValueError(f"the reference does not know the text loaders {sorted(loaders)}")
    return {"seed": k["seed"], "steps": k["steps"], "cfg": k["cfg"],
            "sampler_name": k["sampler_name"], "scheduler": k["scheduler"],
            "positive": texts[0]["inputs"]["text"],
            "negative": texts[1]["inputs"]["text"],
            "text_loader": loaders.pop(), **latent["inputs"]}


class Reference:
    """The served path of one configuration in one arithmetic. Weights go to
    the device in the checkpoint's own type, once per part, and are dropped
    with the object."""

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

    def _part(self, sizes: str) -> dict:
        from . import safetensors_io

        path, part = self._parts[sizes]
        return sd.load_weights(safetensors_io.read(path, part["prefix"]))

    def t5_states(self, ids, mask):
        """The third tower's final states for ids (N, S); a method of its own
        so that a test can put a broken tower in its place."""
        t = self.c["text_t5"]
        w = self._part("text_t5")
        return reference_t5.encode(self.p, w, t, ids,
                                   mask if t.get("attention_mask", True) else None)

    def encode(self, texts: list[str]):
        """→ (context (N, 77 or 77 + T5's length, joint_attention_dim), y (N,
        pooled)): CLIP-L ⊕ bigG penultimate streams zero-padded to the context
        width, then (with a ``text_t5`` block) the T5 states appended along
        the tokens; L pooled (the final-normed state at the first EOS, not
        projected) ⊕ G pooled (the same through ``text_projection``)."""
        c, p = self.c, self.p
        eos = c["text"]["vocab_size"] - 1
        out = []
        for sizes, pad in (("text", None), ("text_g", 0)):
            t = dict(c[sizes], eos_token_id=eos)
            kw = {} if pad is None else {"pad_id": pad}
            ids = jnp.asarray(np.stack(
                [self.tok.ids(s, t["max_position_embeddings"], **kw) for s in texts]))
            w = self._part(sizes)
            if self._parts[sizes][1]["layout"] == "open_clip":
                _, pen, pooled = sd.open_clip_text(p, w, ids, t)
            else:
                _, pen, pooled = sd.clip_hf_text(p, w, ids, t)
                if "text_projection.weight" in w:
                    pooled = sd._ein(p, "bi,oi->bo", pooled,
                                     w["text_projection.weight"].astype(F32))
            out.append((pen, pooled))
            del w
        (pen_l, pool_l), (pen_g, pool_g) = out
        joint = jnp.concatenate([pen_l, pen_g], axis=-1)
        width = c["mmdit"]["joint_attention_dim"]
        context = jnp.pad(joint, ((0, 0), (0, 0), (0, width - joint.shape[-1])))
        if "text_t5" in c:
            ids = np.stack([self.toks["t5"].ids(s) for s in texts])
            states = self.t5_states(jnp.asarray(ids), ids != 0)  # <pad> is id 0
            context = jnp.concatenate([context, states.astype(F32)], axis=1)
        return context, jnp.concatenate([pool_l, pool_g], axis=-1)

    def images(self, req: dict, rows: list[int]) -> np.ndarray:
        """Float images in [0, 1], (len(rows), H, W, 3), for the batch rows
        ``rows`` of one request (``describe``'s keys)."""
        c, p, m = self.c, self.p, self.c["mmdit"]
        if (req["sampler_name"], req["scheduler"]) != ("euler", "sgm_uniform"):
            raise NotImplementedError(
                f"reference has no {req['sampler_name']}/{req['scheduler']}")
        triple = req.get("text_loader") == "TripleCLIPLoader"
        if triple != ("text_t5" in c):
            raise ValueError("a text_t5 block and a TripleCLIPLoader go together")
        context, y = self.encode([req["positive"], req["negative"]])
        h8, w8 = req["height"] // 8, req["width"] // 8
        # The served path draws the whole batch's noise as one NHWC array
        # from jax.random.key(seed): draw it likewise, keep the sampled rows.
        noise = jax.random.normal(jax.random.key(int(req["seed"]) % 2 ** 63),
                                  (req["batch_size"], h8, w8, m["in_channels"]), F32)
        noise = jnp.transpose(noise[jnp.asarray(rows)], (0, 3, 1, 2))
        w = self._part("mmdit")
        scale = float(req["cfg"])

        def velocity(x, sigma):
            # Both halves of classifier-free guidance in one batch, the
            # positive prompt first.
            t = jnp.full((2,), 1000.0 * sigma, F32)
            v = mmdit(p, w, m, jnp.concatenate([x, x]), t, context, y)
            return v[1:] + scale * (v[:1] - v[1:])

        sigmas = sgm_uniform_sigmas(req["steps"], float(c["schedule"]["shift"]),
                                    int(c["schedule"]["timesteps"]))
        # sigma_max is 1: the flow's start is the noise itself. One row at a
        # time, as reference_sd does.
        latents = [sample_euler(velocity, noise[k:k + 1] * float(sigmas[0]),
                                sigmas).block_until_ready() for k in range(len(rows))]
        del w
        w = self._part("vae")
        imgs = [jnp.clip(vae16_decode(p, w, c["vae"], z) * 0.5 + 0.5, 0.0, 1.0)
                for z in latents]
        return np.asarray(jnp.transpose(jnp.concatenate(imgs), (0, 2, 3, 1)), np.float32)
