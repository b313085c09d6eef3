"""Z-Image checkpoint (the published key spelling of ``transformer/``:
``layers.N.attention.to_q.weight``, ``noise_refiner.*``, ``context_refiner.*``,
``cap_embedder.*``, ``all_x_embedder.2-1.*``) → models/zimage.py's param tree.

The load policy is FLUX's (``convert.resident``): every matmul kernel stays in
bfloat16 — as the bfloat16 file stores it, one tensor at a time, so the
6.15 B-parameter family is never whole in float32 on the host or the chip
(the float32-computing embedders' kernels too: widening them back is exact;
from a float32 or fp16 file those would be float32) — and norm scales, pad
tokens and biases are float32."""

from __future__ import annotations

from collections.abc import Mapping
from typing import Any

import jax.numpy as jnp

from .convert import dense_params, to_numpy, tree_to_jnp
from .zimage import ZImageConfig

_PREFIX = "model.diffusion_model."


def strip_zimage_prefix(state_dict: Mapping[str, Any]) -> dict:
    return {k[len(_PREFIX):] if k.startswith(_PREFIX) else k: v
            for k, v in state_dict.items()}


def zimage_depths(keys) -> tuple[int, int]:
    """(``layers``, refiner layers) a Z-Image file holds, from its key names:
    a depth cut of the published model — a contiguous range of the main
    layers, one pipeline stage's share — loads at the depth it has."""
    def count(kind):
        idx = [int(k.split(f"{kind}.", 1)[1].split(".", 1)[0]) for k in keys
               if k.startswith(f"{kind}.") or k.startswith(f"{_PREFIX}{kind}.")]
        return 1 + max(idx) if idx else 0

    return count("layers"), count("noise_refiner")


def convert_zimage_checkpoint(state_dict: Mapping[str, Any], cfg: ZImageConfig) -> dict:
    sd = strip_zimage_prefix(state_dict)
    dt, f32 = cfg.dtype, jnp.float32
    embed = f"{cfg.patch_size}-1"  # "<patch>-<frame patch>": images are one frame

    def dense(key, operand=dt):
        return dense_params(sd, key, operand)

    def scale(key):
        return {"scale": to_numpy(sd[f"{key}.weight"])}

    def block(t, modulated=True):
        out = {
            "attention_norm1": scale(f"{t}.attention_norm1"),
            "attention_norm2": scale(f"{t}.attention_norm2"),
            "ffn_norm1": scale(f"{t}.ffn_norm1"),
            "ffn_norm2": scale(f"{t}.ffn_norm2"),
            "to_q": dense(f"{t}.attention.to_q"),
            "to_k": dense(f"{t}.attention.to_k"),
            "to_v": dense(f"{t}.attention.to_v"),
            "to_out": dense(f"{t}.attention.to_out.0"),
            "norm_q": scale(f"{t}.attention.norm_q"),
            "norm_k": scale(f"{t}.attention.norm_k"),
            "w1": dense(f"{t}.feed_forward.w1"),
            "w2": dense(f"{t}.feed_forward.w2"),
            "w3": dense(f"{t}.feed_forward.w3"),
        }
        if modulated:
            out["adaLN_modulation"] = dense(f"{t}.adaLN_modulation.0", f32)
        return out

    p: dict[str, Any] = {
        "x_embedder": dense(f"all_x_embedder.{embed}"),
        "x_pad_token": to_numpy(sd["x_pad_token"]).reshape(-1),
        "cap_pad_token": to_numpy(sd["cap_pad_token"]).reshape(-1),
        "cap_embedder_0": scale("cap_embedder.0"),
        "cap_embedder_1": dense("cap_embedder.1"),
        "t_embedder_0": dense("t_embedder.mlp.0", f32),
        "t_embedder_2": dense("t_embedder.mlp.2", f32),
        "final_mod": dense(f"all_final_layer.{embed}.adaLN_modulation.1", f32),
        "final_proj": dense(f"all_final_layer.{embed}.linear", f32),
    }
    for i in range(cfg.n_refiner_layers):
        p[f"noise_refiner_{i}"] = block(f"noise_refiner.{i}")
        p[f"context_refiner_{i}"] = block(f"context_refiner.{i}", modulated=False)
    for i in range(cfg.n_layers):
        p[f"layers_{i}"] = block(f"layers.{i}")
    return tree_to_jnp(p)
