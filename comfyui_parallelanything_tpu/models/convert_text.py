"""Text-encoder checkpoints → models/text_encoders.py param trees.

Four source layouts cover the checkpoints the supported model families ship with
(the reference's host app loads these same towers; conditioning arrives at its
``forward(x, t, context)`` boundary pre-encoded, any_device_parallel.py:1287):

- **HF CLIPTextModel** (``text_model.*``): SD1.5's ``cond_stage_model.transformer``
  subtree, SDXL's ``conditioner.embedders.0.transformer``, FLUX's clip_l file.
- **OpenCLIP** (``transformer.resblocks.*`` with fused ``in_proj``): SDXL's
  ``conditioner.embedders.1.model`` subtree.
- **HF T5 encoder** (``encoder.block.*``): FLUX/WAN t5xxl files.
- **HF Qwen3** (``model.layers.*``): Z-Image's ``qwen_3_4b`` file.

Same conventions as convert.py: fp8/f16/bf16 upcast to f32 numpy, torch (out,in)
linears → flax (in,out) kernels, consumed-key tracking absent here because text
checkpoints routinely carry decoder/logit heads we deliberately ignore.
"""

from __future__ import annotations

from collections.abc import Mapping
from typing import Any

import numpy as np

from .convert import dense_params, resident, to_numpy, tree_to_jnp
from .text_encoders import CLIPTextConfig, Qwen3Config, T5Config


def _ln(sd: Mapping[str, Any], key: str) -> dict:
    return {"scale": to_numpy(sd[f"{key}.weight"]), "bias": to_numpy(sd[f"{key}.bias"])}


def _strip(state_dict: Mapping[str, Any], anchor: str) -> dict:
    """Select the encoder subtree by locating ``anchor`` (a key every layout of the
    family contains, e.g. ``token_embedding.weight``), treating everything before it
    as the wrapper prefix (``cond_stage_model.transformer.``,
    ``conditioner.embedders.1.model.`` …) and stripping that prefix from ALL keys —
    sibling keys that don't contain the anchor come along too."""
    for k in state_dict:
        if k.endswith(anchor):
            prefix = k[: len(k) - len(anchor)]
            if not prefix:
                return dict(state_dict)
            return {
                key[len(prefix) :]: v
                for key, v in state_dict.items()
                if key.startswith(prefix)
            }
    return dict(state_dict)


def convert_clip_text_checkpoint(
    state_dict: Mapping[str, Any], cfg: CLIPTextConfig
) -> dict:
    """HF CLIPTextModel layout (``text_model.*``, any wrapper prefix) → CLIPTextModel
    params."""
    sd = _strip(state_dict, "text_model.embeddings.token_embedding.weight")
    p: dict[str, Any] = {
        "tok_emb": {
            "embedding": to_numpy(sd["text_model.embeddings.token_embedding.weight"])
        },
        "pos_emb": to_numpy(sd["text_model.embeddings.position_embedding.weight"]),
        "final_ln": _ln(sd, "text_model.final_layer_norm"),
    }
    for i in range(cfg.num_layers):
        t = f"text_model.encoder.layers.{i}"
        p[f"layers_{i}"] = {
            "ln1": _ln(sd, f"{t}.layer_norm1"),
            "q": dense_params(sd, f"{t}.self_attn.q_proj"),
            "k": dense_params(sd, f"{t}.self_attn.k_proj"),
            "v": dense_params(sd, f"{t}.self_attn.v_proj"),
            "out": dense_params(sd, f"{t}.self_attn.out_proj"),
            "ln2": _ln(sd, f"{t}.layer_norm2"),
            "fc1": dense_params(sd, f"{t}.mlp.fc1"),
            "fc2": dense_params(sd, f"{t}.mlp.fc2"),
        }
    if cfg.projection_dim is not None:
        # HF stores text_projection as a Linear (out,in); some exports as a matrix.
        w = to_numpy(sd["text_projection.weight"])
        p["text_proj"] = {"kernel": w.T}
    return tree_to_jnp(p)


def convert_open_clip_checkpoint(
    state_dict: Mapping[str, Any], cfg: CLIPTextConfig
) -> dict:
    """OpenCLIP text-tower layout (``transformer.resblocks.*``, fused qkv
    ``in_proj``) → CLIPTextModel params. SDXL's second encoder
    (``conditioner.embedders.1.model.*``) is exactly this."""
    # Anchor on a key unique to the OpenCLIP layout: a combined SDXL checkpoint
    # also holds the HF tower's ...text_model.embeddings.token_embedding.weight,
    # so anchoring on token_embedding.weight would lock onto the wrong subtree.
    sd = _strip(state_dict, "positional_embedding")
    if "token_embedding.weight" not in sd:
        raise KeyError("token_embedding.weight not found — not an OpenCLIP text dict")
    H = cfg.hidden_size
    p: dict[str, Any] = {
        "tok_emb": {"embedding": to_numpy(sd["token_embedding.weight"])},
        "pos_emb": to_numpy(sd["positional_embedding"]),
        "final_ln": _ln(sd, "ln_final"),
    }
    for i in range(cfg.num_layers):
        t = f"transformer.resblocks.{i}"
        w = to_numpy(sd[f"{t}.attn.in_proj_weight"])  # (3H, H)
        b = to_numpy(sd[f"{t}.attn.in_proj_bias"])  # (3H,)
        blk: dict[str, Any] = {
            "ln1": _ln(sd, f"{t}.ln_1"),
            "ln2": _ln(sd, f"{t}.ln_2"),
            "out": dense_params(sd, f"{t}.attn.out_proj"),
            "fc1": dense_params(sd, f"{t}.mlp.c_fc"),
            "fc2": dense_params(sd, f"{t}.mlp.c_proj"),
        }
        for j, n in enumerate("qkv"):
            blk[n] = {"kernel": w[j * H : (j + 1) * H].T, "bias": b[j * H : (j + 1) * H]}
        p[f"layers_{i}"] = blk
    if cfg.projection_dim is not None:
        # OpenCLIP's text_projection is a raw (hidden, proj) matrix — NOT a torch
        # Linear — so it maps to the flax kernel without transposition.
        p["text_proj"] = {"kernel": to_numpy(sd["text_projection"])}
    return tree_to_jnp(p)


def convert_t5_checkpoint(state_dict: Mapping[str, Any], cfg: T5Config) -> dict:
    """HF T5 v1.1 layout → T5Encoder params (encoder stack only; decoder/lm_head
    keys in full-model checkpoints are ignored). The bias-free matmul kernels
    and the embedding stay in their resident type (``convert.resident``:
    bfloat16 under the tower's bfloat16 compute, so T5-XXL is 9.5 GB and never
    whole in float32); norm scales and the position table are float32."""
    sd = _strip(state_dict, "encoder.final_layer_norm.weight")
    emb_key = "shared.weight" if "shared.weight" in sd else "encoder.embed_tokens.weight"
    p: dict[str, Any] = {
        "tok_emb": {"embedding": resident(sd[emb_key], cfg.dtype)},
        "final_ln": {"scale": to_numpy(sd["encoder.final_layer_norm.weight"])},
    }
    rel = ".layer.0.SelfAttention.relative_attention_bias.weight"
    if cfg.per_layer_bias:
        # UMT5: one table per layer.
        for i in range(cfg.num_layers):
            p[f"rel_bias_{i}"] = to_numpy(sd[f"encoder.block.{i}{rel}"])
    else:
        p["rel_bias"] = to_numpy(sd[f"encoder.block.0{rel}"])

    def dense(key):
        return dense_params(sd, key, cfg.dtype)

    for i in range(cfg.num_layers):
        t = f"encoder.block.{i}"
        p[f"blocks_{i}"] = {
            "ln1": {"scale": to_numpy(sd[f"{t}.layer.0.layer_norm.weight"])},
            "q": dense(f"{t}.layer.0.SelfAttention.q"),
            "k": dense(f"{t}.layer.0.SelfAttention.k"),
            "v": dense(f"{t}.layer.0.SelfAttention.v"),
            "o": dense(f"{t}.layer.0.SelfAttention.o"),
            "ln2": {"scale": to_numpy(sd[f"{t}.layer.1.layer_norm.weight"])},
            "wi_0": dense(f"{t}.layer.1.DenseReluDense.wi_0"),
            "wi_1": dense(f"{t}.layer.1.DenseReluDense.wi_1"),
            "wo": dense(f"{t}.layer.1.DenseReluDense.wo"),
        }
    return tree_to_jnp(p)


def convert_qwen3_checkpoint(state_dict: Mapping[str, Any], cfg: Qwen3Config) -> dict:
    """HF ``Qwen3ForCausalLM`` / ``Qwen3Model`` layout (``model.layers.N.*``,
    any wrapper prefix; Qwen2.5-VL's language model spells it the same, with
    biases on q / k / v and no q/k norms: ``cfg.qkv_bias`` / ``cfg.qk_norm``)
    → Qwen3Model params, for the ``cfg.output_layers`` layers the tower runs:
    without ``cfg.final_norm`` the last layer and ``model.norm`` are left in
    the file, a ``lm_head`` and a ``visual`` tower always. Matmul kernels and the embedding stay in their
    resident type (``convert.resident``: bfloat16 from a bfloat16 file or
    under bfloat16 compute — 7.8 GB, never whole in float32); the RMS scales
    are float32."""
    sd = _strip(state_dict, "model.embed_tokens.weight")

    def dense(key):
        return dense_params(sd, key, cfg.dtype)

    def scale(key):
        return {"scale": to_numpy(sd[f"{key}.weight"])}

    p: dict[str, Any] = {
        "embed_tokens": {"embedding": resident(sd["model.embed_tokens.weight"], cfg.dtype)},
    }
    for i in range(cfg.output_layers):
        t = f"model.layers.{i}"
        p[f"layers_{i}"] = {
            "input_layernorm": scale(f"{t}.input_layernorm"),
            "q_proj": dense(f"{t}.self_attn.q_proj"),
            "k_proj": dense(f"{t}.self_attn.k_proj"),
            "v_proj": dense(f"{t}.self_attn.v_proj"),
            "o_proj": dense(f"{t}.self_attn.o_proj"),
            "post_attention_layernorm": scale(f"{t}.post_attention_layernorm"),
            "gate_proj": dense(f"{t}.mlp.gate_proj"),
            "up_proj": dense(f"{t}.mlp.up_proj"),
            "down_proj": dense(f"{t}.mlp.down_proj"),
        }
        if cfg.qk_norm:
            p[f"layers_{i}"]["q_norm"] = scale(f"{t}.self_attn.q_norm")
            p[f"layers_{i}"]["k_norm"] = scale(f"{t}.self_attn.k_norm")
    if cfg.final_norm:
        p["norm"] = scale("model.norm")
    return tree_to_jnp(p)
