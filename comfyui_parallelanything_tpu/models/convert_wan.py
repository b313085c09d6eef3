"""WAN video-DiT checkpoint (official Wan2.x layout) → models/wan.py param tree.

The reference lists WAN2.2 among its tested workloads (/root/reference/README.md:5)
and replicates the torch module per device; here the official safetensors layout
converts once into the functional param tree. Layout map (module names on the left
are the public Wan2.x release's):

- ``patch_embedding``            — Conv3d with kernel == stride == patch_size; its
  (O, C, pt, ph, pw) weight folds into our patchify Dense by transposing to
  (pt, ph, pw, C, O) and flattening — exactly the (pt, ph, pw, C) token order
  WanModel.prepare emits.
- ``text_embedding.0/.2``        → ``text_in`` / ``text_hidden``
- ``time_embedding.0/.2``        → ``time_in`` / ``time_hidden``
- ``time_projection.1``          → ``time_projection``
- ``blocks.{i}.self_attn.{q,k,v,o}``        → ``blocks_{i}.self_{q,k,v,o}``
- ``blocks.{i}.self_attn.norm_{q,k}.weight``→ ``blocks_{i}.self_{q,k}_norm.scale``
- ``blocks.{i}.cross_attn...``              → ``blocks_{i}.cross_*`` (same pattern)
- ``blocks.{i}.norm3.{weight,bias}``        → ``blocks_{i}.norm3`` (affine pre-norm;
  norm1/norm2 are affine-free in both implementations — no weights to map)
- ``blocks.{i}.ffn.0/.2``                   → ``blocks_{i}.ffn_in`` / ``ffn_out``
- ``blocks.{i}.modulation``                 → ``blocks_{i}.modulation`` (1, 6, D)
- ``head.head``                             → ``head_proj``
- ``head.modulation``                       → ``head_modulation`` (1, 2, D)

The WAN2.1-style i2v CLIP-image branch converts when the config carries
``img_dim`` (wan_14b_i2v_clip_config):

- ``img_emb.proj.0/.1/.3/.4`` → ``img_ln_in`` / ``img_in`` / ``img_hidden`` /
  ``img_ln_out`` (the MLPProj LN→Dense→GELU→Dense→LN stack)
- ``blocks.{i}.cross_attn.{k,v}_img``   → ``blocks_{i}.cross_{k,v}_img``
- ``blocks.{i}.cross_attn.norm_k_img.weight`` → ``blocks_{i}.cross_k_img_norm``

Without ``img_dim`` those keys are ignored (a t2v config loading an i2v file);
ema/optimizer sidecars are always ignored.
"""

from __future__ import annotations

from collections.abc import Mapping
from typing import Any

import jax.numpy as jnp

from .convert import dense_params as _dense, resident, to_numpy, tree_to_jnp
from .wan import WanConfig


def wan_depth(keys) -> int:
    """How many blocks a WAN-layout file holds, from its key names: a depth
    cut of a published model — a contiguous block range, one pipeline stage's
    share — loads at the depth it has."""
    idx = [int(k.split(".")[1]) for k in keys
           if k.startswith("blocks.") and k.split(".")[1].isdigit()]
    return 1 + max(idx) if idx else 0


def _rms(sd: Mapping[str, Any], key: str) -> dict:
    return {"scale": to_numpy(sd[f"{key}.weight"])}


def _ln(sd: Mapping[str, Any], key: str) -> dict:
    return {"scale": to_numpy(sd[f"{key}.weight"]), "bias": to_numpy(sd[f"{key}.bias"])}


def convert_wan_checkpoint(state_dict: Mapping[str, Any], cfg: WanConfig) -> dict:
    """Official WAN state dict → the param pytree of ``models.wan.WanModel``
    (pass to ``build_wan(cfg, params=...)``).

    Matmul kernels stay in their resident type (``convert.resident``: bfloat16
    from a bfloat16 file or under bfloat16 compute, one tensor at a time —
    14 B parameters an expert never exist whole in float32); norm scales,
    biases and the modulation tables are float32. ``state_dict`` is read key
    by key and never copied, so a lazily baked one (``convert.bake_lora``)
    bakes a tensor as it is taken."""
    sd = state_dict
    dt, f32 = cfg.dtype, jnp.float32

    # Conv3d patchify (O, C, pt, ph, pw) → Dense kernel (pt·ph·pw·C, O) in the
    # (pt, ph, pw, C) flattening order of WanModel.prepare.
    w = jnp.asarray(resident(sd["patch_embedding.weight"], dt))
    pe_kernel = w.transpose(2, 3, 4, 1, 0).reshape(-1, w.shape[0])
    p: dict[str, Any] = {
        "patch_embedding": {
            "kernel": pe_kernel,
            "bias": to_numpy(sd["patch_embedding.bias"]),
        },
        "text_in": _dense(sd, "text_embedding.0", dt),
        "text_hidden": _dense(sd, "text_embedding.2", dt),
        # the time path and the head compute in float32 (WanModel.setup)
        "time_in": _dense(sd, "time_embedding.0", f32),
        "time_hidden": _dense(sd, "time_embedding.2", f32),
        "time_projection": _dense(sd, "time_projection.1", f32),
        "head_proj": _dense(sd, "head.head", f32),
        "head_modulation": {"bias": to_numpy(sd["head.modulation"])},
    }
    if cfg.img_dim is not None:
        p["img_ln_in"] = _ln(sd, "img_emb.proj.0")
        p["img_in"] = _dense(sd, "img_emb.proj.1", dt)
        p["img_hidden"] = _dense(sd, "img_emb.proj.3", dt)
        p["img_ln_out"] = _ln(sd, "img_emb.proj.4")
    for i in range(cfg.depth):
        t = f"blocks.{i}"
        p[f"blocks_{i}"] = {
            "self_q": _dense(sd, f"{t}.self_attn.q", dt),
            "self_k": _dense(sd, f"{t}.self_attn.k", dt),
            "self_v": _dense(sd, f"{t}.self_attn.v", dt),
            "self_o": _dense(sd, f"{t}.self_attn.o", dt),
            "self_q_norm": _rms(sd, f"{t}.self_attn.norm_q"),
            "self_k_norm": _rms(sd, f"{t}.self_attn.norm_k"),
            "cross_q": _dense(sd, f"{t}.cross_attn.q", dt),
            "cross_k": _dense(sd, f"{t}.cross_attn.k", dt),
            "cross_v": _dense(sd, f"{t}.cross_attn.v", dt),
            "cross_o": _dense(sd, f"{t}.cross_attn.o", dt),
            "cross_q_norm": _rms(sd, f"{t}.cross_attn.norm_q"),
            "cross_k_norm": _rms(sd, f"{t}.cross_attn.norm_k"),
            "norm3": _ln(sd, f"{t}.norm3"),
            "ffn_in": _dense(sd, f"{t}.ffn.0", dt),
            "ffn_out": _dense(sd, f"{t}.ffn.2", dt),
            "modulation": to_numpy(sd[f"{t}.modulation"]),
        }
        if cfg.img_dim is not None:
            p[f"blocks_{i}"].update(
                cross_k_img=_dense(sd, f"{t}.cross_attn.k_img", dt),
                cross_v_img=_dense(sd, f"{t}.cross_attn.v_img", dt),
                cross_k_img_norm=_rms(sd, f"{t}.cross_attn.norm_k_img"),
            )
    return tree_to_jnp(p)
