"""The Z-Image-Turbo file layouts, written out from the published
descriptions: Tongyi-MAI/Z-Image ``src/zimage/transformer.py`` (the
single-stream transformer as ``transformer/`` holds it, bare keys), the HF
``Qwen3ForCausalLM`` of ``text_encoder/`` (Qwen3-4B; embeddings tied, so no
``lm_head``) and — imported, not copied — the 16-channel ``AutoEncoder`` of
``ae.safetensors`` (``layout_mmdit``). Each function returns
``[(key, shape, kind)]`` like ``layout.py``'s; ``synth.write_checkpoint``
finds them through the configuration's ``checkpoint.layouts``. The sizes are
the ``config.json`` files' under their own names (``dim``, ``n_layers``,
``n_refiner_layers``, ...; ``hidden_size``, ``num_hidden_layers``, ...), so
the depth cut is one number in the configuration. Nothing here imports the
program."""

from __future__ import annotations

from .layout import _lin
from .layout_mmdit import vae16_layout

__all__ = ["zimage_layout", "qwen3_layout", "vae16_layout", "head_dim",
           "ffn_hidden", "adaln_dim"]

ADALN_EMBED_DIM = 256       # transformer.py's constant
T_EMBEDDER_MID = 1024       # TimestepEmbedder(mid_size=1024)
FREQUENCY_EMBEDDING = 256


def head_dim(m: dict) -> int:
    return m["dim"] // m["n_heads"]


def ffn_hidden(m: dict) -> int:
    """``FeedForward(dim, hidden_dim=int(dim / 3 * 8))``."""
    return int(m["dim"] / 3 * 8)


def adaln_dim(m: dict) -> int:
    return min(m["dim"], ADALN_EMBED_DIM)


def _rms(out, key, n):
    out.append((f"{key}.weight", (n,), "norm"))


def _block(out, b, m, modulated):
    """``ZImageTransformerBlock``: bias-free q / k / v / out projections with
    per-head RMS norms of q and k, the bias-free SwiGLU ``w1`` / ``w2`` /
    ``w3``, four RMS norms of the stream, and — unless it is a context
    refiner's — the modulation's one linear layer to four vectors."""
    d, hd, ff = m["dim"], head_dim(m), ffn_hidden(m)
    for n in ("to_q", "to_k", "to_v"):
        _lin(out, f"{b}.attention.{n}", d, d, bias=False)
    _lin(out, f"{b}.attention.to_out.0", d, d, bias=False)
    if m.get("qk_norm", True):
        _rms(out, f"{b}.attention.norm_q", hd)
        _rms(out, f"{b}.attention.norm_k", hd)
    _lin(out, f"{b}.feed_forward.w1", d, ff, bias=False)
    _lin(out, f"{b}.feed_forward.w2", ff, d, bias=False)
    _lin(out, f"{b}.feed_forward.w3", d, ff, bias=False)
    for n in ("attention_norm1", "ffn_norm1", "attention_norm2", "ffn_norm2"):
        _rms(out, f"{b}.{n}", d)
    if modulated:
        _lin(out, f"{b}.adaLN_modulation.0", adaln_dim(m), 4 * d)


def zimage_layout(m: dict) -> list[tuple]:
    """``ZImageTransformer2DModel`` for one patch size (``all_patch_size``
    (2,), ``all_f_patch_size`` (1,): the module dicts' one key is "2-1"):
    the patch embedder and the final layer, ``noise_refiner`` (modulated) and
    ``context_refiner`` (not) of ``n_refiner_layers`` blocks each, the
    timestep embedder (256 → 1024 → 256), the caption embedder (RMS norm,
    linear), the two learned pad tokens, ``n_layers`` modulated blocks. The
    pad tokens are drawn at unit variance (kernels of fan-in 1): at the small
    ``emb`` scale they would vanish beside the O(1) tokens they stand among."""
    out: list[tuple] = []
    d, p = m["dim"], m["all_patch_size"][0]
    key = f"{p}-{m['all_f_patch_size'][0]}"
    patch = p * p * m["all_f_patch_size"][0] * m["in_channels"]
    _lin(out, f"all_x_embedder.{key}", patch, d)
    _lin(out, f"all_final_layer.{key}.linear", d, patch)
    _lin(out, f"all_final_layer.{key}.adaLN_modulation.1", adaln_dim(m), d)
    for i in range(m["n_refiner_layers"]):
        _block(out, f"noise_refiner.{i}", m, True)
    for i in range(m["n_refiner_layers"]):
        _block(out, f"context_refiner.{i}", m, False)
    _lin(out, "t_embedder.mlp.0", FREQUENCY_EMBEDDING, T_EMBEDDER_MID)
    _lin(out, "t_embedder.mlp.2", T_EMBEDDER_MID, adaln_dim(m))
    _rms(out, "cap_embedder.0", m["cap_feat_dim"])
    _lin(out, "cap_embedder.1", m["cap_feat_dim"], d)
    out.append(("x_pad_token", (1, d), "w:1"))
    out.append(("cap_pad_token", (1, d), "w:1"))
    for i in range(m["n_layers"]):
        _block(out, f"layers.{i}", m, True)
    return out


def qwen3_layout(t: dict) -> list[tuple]:
    """HF ``Qwen3ForCausalLM`` with tied embeddings: ``model.embed_tokens``,
    per layer the bias-free q (``num_attention_heads`` x ``head_dim``), k and
    v (``num_key_value_heads`` x ``head_dim``) and o projections, the RMS
    norms of q and k over the head dim, the bias-free SwiGLU ``gate_proj`` /
    ``up_proj`` / ``down_proj`` and two RMS norms; ``model.norm``. The
    embedding is drawn at unit variance (a kernel of fan-in 1), as
    ``layout_mmdit.t5_layout`` says: it is normed before any product, and at
    the small ``emb`` scale the first layers' outputs would bury the tokens."""
    out: list[tuple] = []
    h, hd, ff = t["hidden_size"], t["head_dim"], t["intermediate_size"]
    q, kv = t["num_attention_heads"] * hd, t["num_key_value_heads"] * hd
    out.append(("model.embed_tokens.weight", (t["vocab_size"], h), "w:1"))
    for i in range(t["num_hidden_layers"]):
        b = f"model.layers.{i}"
        _lin(out, f"{b}.self_attn.q_proj", h, q, bias=False)
        _lin(out, f"{b}.self_attn.k_proj", h, kv, bias=False)
        _lin(out, f"{b}.self_attn.v_proj", h, kv, bias=False)
        _lin(out, f"{b}.self_attn.o_proj", q, h, bias=False)
        _rms(out, f"{b}.self_attn.q_norm", hd)
        _rms(out, f"{b}.self_attn.k_norm", hd)
        _lin(out, f"{b}.mlp.gate_proj", h, ff, bias=False)
        _lin(out, f"{b}.mlp.up_proj", h, ff, bias=False)
        _lin(out, f"{b}.mlp.down_proj", ff, h, bias=False)
        _rms(out, f"{b}.input_layernorm", h)
        _rms(out, f"{b}.post_attention_layernorm", h)
    _rms(out, "model.norm", h)
    return out
