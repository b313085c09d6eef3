"""Qwen-Image checkpoint (the published key spelling of ``transformer/``, which
ComfyUI's ``qwen_image_bf16.safetensors`` keeps: ``transformer_blocks.N.attn.
to_q`` / ``add_q_proj`` / ``norm_q`` / ``norm_added_q`` / ``to_out.0`` /
``to_add_out``, ``img_mod.1``, ``img_mlp.net.0.proj`` / ``net.2``, ``img_in``,
``txt_norm``, ``txt_in``, ``time_text_embed.timestep_embedder.linear_1/2``,
``norm_out.linear``, ``proj_out``) → models/qwen_image.py's param tree, whose
blocks are ``models/flux.DoubleBlock``'s.

The load policy is FLUX's (``convert.resident``): every matmul kernel stays in
bfloat16 — as the bfloat16 file stores it, one tensor at a time, so the
20 B-parameter family (or a chip's share of it) is never whole in float32 on
the host or the chip — and norm scales and biases are float32. The three
projections of a stream are laid side by side into the block's one fused
kernel (in, 3, H, D). The file orders a token's 2 x 2-patch features
(c, ph, pw) — the pipeline's ``_pack_latents`` — and the model's patchify
orders them (ph, pw, c): ``img_in``'s input rows and ``proj_out``'s output
columns are permuted here, once. ``state_dict`` is read key by key and never
copied, so a lazily baked one (``convert.bake_lora``) bakes a tensor as it is
taken."""

from __future__ import annotations

from collections.abc import Mapping
from typing import Any

import jax.numpy as jnp

from .convert import dense_params, resident, to_numpy, tree_to_jnp
from .qwen_image import QwenImageConfig

_PREFIXES = ("model.diffusion_model.", "transformer.")


def _bare(key: str) -> str:
    return next((key[len(p):] for p in _PREFIXES if key.startswith(p)), key)


def qwen_image_depth(keys) -> int:
    """How many blocks a Qwen-Image file holds, from its key names: a depth
    cut of the published model — a contiguous block range, one pipeline
    stage's share — loads at the depth it has."""
    idx = [int(k.split(".")[1]) for k in map(_bare, keys)
           if k.startswith("transformer_blocks.") and k.split(".")[1].isdigit()]
    return 1 + max(idx) if idx else 0


class _Bare(Mapping):
    """The file's keys without a wrapper's prefix, values taken through to
    the mapping underneath (a lazily baked one stays lazy)."""

    def __init__(self, sd: Mapping[str, Any]):
        self._sd = sd
        self._keys = {_bare(k): k for k in sd}

    def __getitem__(self, key):
        return self._sd[self._keys[key]]

    def __contains__(self, key):
        return key in self._keys  # asked of the names: nothing is taken

    def __iter__(self):
        return iter(self._keys)

    def __len__(self):
        return len(self._keys)


def convert_qwen_image_checkpoint(state_dict: Mapping[str, Any],
                                  cfg: QwenImageConfig) -> dict:
    sd = _Bare(state_dict)
    H, D = cfg.num_heads, cfg.head_dim
    dt, f32 = cfg.dtype, jnp.float32
    pp = cfg.patch_size ** 2
    ch = cfg.in_channels // pp

    def dense(key, operand=dt):
        return dense_params(sd, key, operand)

    def patch_order(a, axis):
        """Features (c, ph, pw) → (ph, pw, c) along ``axis``."""
        a = jnp.asarray(a)
        shape = a.shape
        a = a.reshape(shape[:axis] + (ch, pp) + shape[axis + 1:])
        return jnp.swapaxes(a, axis, axis + 1).reshape(shape)

    def fused_qkv(t, names):
        """Three (H·D, in) projections → FusedQKV's kernel (in, 3, H, D) and
        bias (3, H, D), each kernel in its resident type before they meet."""
        ws = [jnp.asarray(resident(sd[f"{t}.{n}.weight"], dt)) for n in names]
        kernel = jnp.stack(ws).reshape(3, H, D, ws[0].shape[1]).transpose(3, 0, 1, 2)
        bias = jnp.stack([jnp.asarray(to_numpy(sd[f"{t}.{n}.bias"])) for n in names])
        return {"kernel": kernel, "bias": bias.reshape(3, H, D)}

    def qk_norm(t, q, k):
        return {"query_norm": to_numpy(sd[f"{t}.{q}.weight"]),
                "key_norm": to_numpy(sd[f"{t}.{k}.weight"])}

    p: dict[str, Any] = {
        "img_in": dense("img_in"),
        "txt_norm": {"scale": to_numpy(sd["txt_norm.weight"])},
        "txt_in": dense("txt_in"),
        "time_in": {
            "in_layer": dense("time_text_embed.timestep_embedder.linear_1"),
            "out_layer": dense("time_text_embed.timestep_embedder.linear_2")},
        # the modulations and the head compute in float32 (flux.Modulation)
        "final_mod": dense("norm_out.linear", f32),
        "final_proj": {k: patch_order(v, v.ndim - 1)
                       for k, v in dense("proj_out", f32).items()},
    }
    p["img_in"]["kernel"] = patch_order(p["img_in"]["kernel"], 0)
    for i in range(cfg.depth):
        t = f"transformer_blocks.{i}"
        p[f"transformer_blocks_{i}"] = {
            "img_mod": {"lin": dense(f"{t}.img_mod.1", f32)},
            "txt_mod": {"lin": dense(f"{t}.txt_mod.1", f32)},
            "img_attn_qkv": fused_qkv(f"{t}.attn", ("to_q", "to_k", "to_v")),
            "txt_attn_qkv": fused_qkv(
                f"{t}.attn", ("add_q_proj", "add_k_proj", "add_v_proj")),
            "img_attn_norm": qk_norm(f"{t}.attn", "norm_q", "norm_k"),
            "txt_attn_norm": qk_norm(f"{t}.attn", "norm_added_q", "norm_added_k"),
            "img_attn_proj": dense(f"{t}.attn.to_out.0"),
            "txt_attn_proj": dense(f"{t}.attn.to_add_out"),
            "img_mlp_in": dense(f"{t}.img_mlp.net.0.proj"),
            "img_mlp_out": dense(f"{t}.img_mlp.net.2"),
            "txt_mlp_in": dense(f"{t}.txt_mlp.net.0.proj"),
            "txt_mlp_out": dense(f"{t}.txt_mlp.net.2"),
        }
    return tree_to_jnp(p)
