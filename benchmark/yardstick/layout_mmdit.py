"""The SD3-family single-file checkpoint layout (``*_incl_clips``), written out
from the published descriptions: Stability-AI/sd3.5 ``mmditx.py`` (the MMDiT-X
under ``model.diffusion_model.``), the 16-channel ``AutoencoderKL`` without
quant convolutions (``first_stage_model.``) and the two CLIP text towers under
``text_encoders.clip_l.transformer.`` / ``text_encoders.clip_g.transformer.``,
both in the HF ``CLIPTextModel`` layout (bigG with its ``text_projection`` as a
``Linear``). Each function returns ``[(key, shape, kind)]`` like ``layout.py``'s;
``synth.write_checkpoint`` finds them through the configuration's
``checkpoint.layouts``. Nothing here imports the program."""

from __future__ import annotations

from .layout import _lin, _norm, clip_hf_layout, open_clip_layout, vae_layout

# open_clip_layout: bigG as open_clip writes it, in a tower file of its own
__all__ = ["mmdit_layout", "vae16_layout", "clip_hf_layout", "clip_g_hf_layout",
           "open_clip_layout", "t5_layout", "hidden_size", "dual_layers"]


def hidden_size(m: dict) -> int:
    return m["num_attention_heads"] * m["attention_head_dim"]


def dual_layers(m: dict) -> list[int]:
    return list(m["dual_attention_layers"])


def mmdit_layout(m: dict) -> list[tuple]:
    """``MMDiTX``: patch embedding (a p x p convolution), the stored sincos
    position table, context / timestep / pooled-vector embedders,
    ``num_layers`` joint blocks (the last one's context block is pre-only:
    modulation and qkv, no output path), the final layer."""
    out: list[tuple] = []
    h, p, c = hidden_size(m), m["patch_size"], m["in_channels"]
    d = m["attention_head_dim"]
    mlp = int(h * m.get("mlp_ratio", 4.0))
    out.append(("x_embedder.proj.weight", (h, c, p, p), f"w:{c * p * p}"))
    out.append(("x_embedder.proj.bias", (h,), "bias"))
    # The stored table: synth.py draws kernels, norms, biases; as a kernel of
    # fan-in 2 it has the sincos table's RMS, 1/sqrt(2).
    out.append(("pos_embed", (1, m["pos_embed_max_size"] ** 2, h), "w:2"))
    _lin(out, "context_embedder", m["joint_attention_dim"], h)
    _lin(out, "t_embedder.mlp.0", m["frequency_embedding_size"], h)
    _lin(out, "t_embedder.mlp.2", h, h)
    _lin(out, "y_embedder.mlp.0", m["pooled_projection_dim"], h)
    _lin(out, "y_embedder.mlp.2", h, h)

    def attn(key, pre_only=False):
        _lin(out, f"{key}.qkv", h, 3 * h)
        if m.get("qk_norm") == "rms_norm":
            out.append((f"{key}.ln_q.weight", (d,), "norm"))
            out.append((f"{key}.ln_k.weight", (d,), "norm"))
        if not pre_only:
            _lin(out, f"{key}.proj", h, h)

    for i in range(m["num_layers"]):
        last = i == m["num_layers"] - 1
        b = f"joint_blocks.{i}.context_block"
        _lin(out, f"{b}.adaLN_modulation.1", h, (2 if last else 6) * h)
        attn(f"{b}.attn", pre_only=last)
        if not last:
            _lin(out, f"{b}.mlp.fc1", h, mlp)
            _lin(out, f"{b}.mlp.fc2", mlp, h)
        b = f"joint_blocks.{i}.x_block"
        dual = i in dual_layers(m)
        _lin(out, f"{b}.adaLN_modulation.1", h, (9 if dual else 6) * h)
        attn(f"{b}.attn")
        if dual:
            attn(f"{b}.attn2")
        _lin(out, f"{b}.mlp.fc1", h, mlp)
        _lin(out, f"{b}.mlp.fc2", mlp, h)
    _lin(out, "final_layer.adaLN_modulation.1", h, 2 * h)
    _lin(out, "final_layer.linear", h, p * p * m["out_channels"])
    return out


def vae16_layout(v: dict) -> list[tuple]:
    """The kl-f8 layout without ``quant_conv`` / ``post_quant_conv``."""
    return [e for e in vae_layout(dict(v, embed_dim=v["z_channels"]))
            if "quant_conv." not in e[0]]


def clip_g_hf_layout(c: dict) -> list[tuple]:
    """bigG as the bundle writes it: HF ``CLIPTextModel`` keys and a
    ``text_projection`` stored as a ``Linear`` (projection, hidden)."""
    h = c["hidden_size"]
    return clip_hf_layout(c) + [
        ("text_projection.weight", (c["projection_dim"], h), f"w:{h}")]


def t5_layout(t: dict) -> list[tuple]:
    """The T5 v1.1 encoder as the public ``t5xxl`` files hold it (HF
    ``T5EncoderModel`` keys, ``config.json`` size names): ``shared.weight``,
    per block an RMS norm, bias-free q / k / v / o of inner width
    ``num_heads * d_kv``, a second RMS norm and the gated feed-forward
    ``wi_0`` / ``wi_1`` / ``wo``; the relative-position table in block 0 only
    (every block with ``per_layer_bias``, UMT5); ``encoder.final_layer_norm``.
    The embedding and the position table are drawn at unit variance (kernels of
    fan-in 1): T5 adds the bias to unscaled logits and norms the embedding
    before any product, so the small ``emb`` kind would switch both off."""
    out: list[tuple] = []
    d, ff, heads = t["d_model"], t["d_ff"], t["num_heads"]
    inner = heads * t["d_kv"]
    out.append(("shared.weight", (t["vocab_size"], d), "w:1"))
    for i in range(t["num_layers"]):
        a = f"encoder.block.{i}.layer.0"
        for n in "qkv":
            _lin(out, f"{a}.SelfAttention.{n}", d, inner, bias=False)
        _lin(out, f"{a}.SelfAttention.o", inner, d, bias=False)
        if i == 0 or t.get("per_layer_bias"):
            out.append((f"{a}.SelfAttention.relative_attention_bias.weight",
                        (t["relative_attention_num_buckets"], heads), "w:1"))
        out.append((f"{a}.layer_norm.weight", (d,), "norm"))
        f = f"encoder.block.{i}.layer.1"
        _lin(out, f"{f}.DenseReluDense.wi_0", d, ff, bias=False)
        _lin(out, f"{f}.DenseReluDense.wi_1", d, ff, bias=False)
        _lin(out, f"{f}.DenseReluDense.wo", ff, d, bias=False)
        out.append((f"{f}.layer_norm.weight", (d,), "norm"))
    out.append(("encoder.final_layer_norm.weight", (d,), "norm"))
    return out
