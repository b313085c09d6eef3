"""Torch-checkpoint → JAX parameter conversion, with LoRA baking.

SURVEY §7 hard parts 2 and 5: the one place torch legitimately remains is CPU-side
checkpoint loading. The reference replicates live torch modules, preserving fp8-stored
weights and LoRA patches through cloning (any_device_parallel.py:93-124, 688-699,
971-1004). Here the equivalents are:

- fp8-on-disk weights upcast at load — v5e has no fp8 matmul path, so fp8 tensors
  become the model's compute dtype on conversion (parity: fp8→fp16 downcast on
  non-fp8 devices, 688-699);
- LoRA is baked into the base weights *before* conversion (``bake_lora``) — the
  analogue of the reference's bake-before-replicate ``patch_model(device_to=...)``
  call (992-1004): one merged weight set, replicated by sharding, no per-step patch
  math;
- name/layout mapping: torch ``Linear.weight`` is (out, in) → flax ``kernel`` is
  (in, out); torch ``Conv2d.weight`` is (O, I, kH, kW) → flax (kH, kW, I, O); fused
  qkv (3·H·D, in) → DenseGeneral kernels (in, 3, H, D).

All functions take a flat ``{name: tensor}`` state dict (torch tensors or numpy
arrays) and return JAX pytrees; no torch import is required unless torch tensors are
actually passed in.
"""

from __future__ import annotations

from collections.abc import Mapping
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.logging import get_logger
from .flux import FluxConfig

_FP8_DTYPE_NAMES = (
    # Parity: is_float8_dtype's five-name string match (93-98).
    "float8_e4m3fn",
    "float8_e4m3fnuz",
    "float8_e5m2",
    "float8_e5m2fnuz",
    "float8_e8m0fnu",
)


def is_float8_dtype(dtype: Any) -> bool:
    """String-matched fp8 detection, torch- and numpy-dtype agnostic (parity 93-98)."""
    return any(name in str(dtype) for name in _FP8_DTYPE_NAMES)


def to_numpy(t: Any) -> np.ndarray:
    """Any checkpoint tensor → float32 numpy. fp8/bf16/f16 upcast to f32 here; the
    model's compute dtype policy re-casts at apply time (bf16 matmuls on TPU)."""
    if isinstance(t, np.ndarray):
        return t.astype(np.float32) if t.dtype != np.float32 else t
    # torch tensor (duck-typed so numpy-only callers never import torch)
    if hasattr(t, "detach"):
        t = t.detach()
        if is_float8_dtype(t.dtype) or str(t.dtype) in ("torch.bfloat16", "torch.float16"):
            t = t.float()
        return t.cpu().numpy().astype(np.float32)
    return np.asarray(t, dtype=np.float32)


def resident(t: Any, operand_dtype: Any = None):
    """A matmul kernel or embedding table in the type it stays resident in —
    the load policy of the families whose towers do not fit a chip in float32
    (the FLUX denoiser, T5-XXL): bfloat16 where the file stores bfloat16
    (widening it back is exact, whatever type the module computes in) or where
    the module casts its operands to bfloat16 at every use (rounded once here:
    the same values); float32 otherwise, as every other family's loader keeps
    it. The cast runs on the device, one tensor at a time, so a 16-bit file
    never exists whole in float32 on the host or the chip."""
    if not isinstance(t, (np.ndarray, jax.Array)):
        t = to_numpy(t)
    if t.dtype == jnp.bfloat16:
        return jnp.asarray(t)
    if operand_dtype is not None and jnp.dtype(operand_dtype) == jnp.bfloat16:
        return jnp.asarray(t).astype(jnp.bfloat16)
    return to_numpy(t)


# --------------------------------------------------------------------------------------
# Layout transforms (torch → flax)
# --------------------------------------------------------------------------------------


def linear_kernel(w: Any) -> np.ndarray:
    """(out, in) → (in, out)."""
    return to_numpy(w).T


def conv_kernel(w: Any) -> np.ndarray:
    """(O, I, kH, kW) → (kH, kW, I, O)."""
    return to_numpy(w).transpose(2, 3, 1, 0)


def qkv_kernel(w: Any, heads: int, head_dim: int, operand_dtype: Any = None):
    """Fused qkv (3·H·D, in) → DenseGeneral kernel (in, 3, H, D); in its
    resident type where ``operand_dtype`` is given (``resident``)."""
    arr = to_numpy(w) if operand_dtype is None else resident(w, operand_dtype)
    in_dim = arr.shape[1]
    return arr.reshape(3, heads, head_dim, in_dim).transpose(3, 0, 1, 2)


def qkv_bias(b: Any, heads: int, head_dim: int) -> np.ndarray:
    """(3·H·D,) → (3, H, D)."""
    return to_numpy(b).reshape(3, heads, head_dim)


# --------------------------------------------------------------------------------------
# LoRA baking (bake-before-convert; parity: patch_model at 992-1004)
# --------------------------------------------------------------------------------------


def _lora_pairs(lora_sd: Mapping[str, Any]) -> dict[str, tuple[Any, Any, float | None]]:
    """Collect (down/A, up/B, alpha) per base key from either naming convention:
    kohya ``{base}.lora_down.weight`` / ``.lora_up.weight`` / ``.alpha`` or
    diffusers/PEFT ``{base}.lora_A.weight`` / ``.lora_B.weight``."""
    pairs: dict[str, dict[str, Any]] = {}
    for key, tensor in lora_sd.items():
        for down_tag, up_tag in ((".lora_down.weight", ".lora_up.weight"),
                                 (".lora_A.weight", ".lora_B.weight")):
            if key.endswith(down_tag):
                pairs.setdefault(key[: -len(down_tag)], {})["down"] = tensor
                break
            if key.endswith(up_tag):
                pairs.setdefault(key[: -len(up_tag)], {})["up"] = tensor
                break
        else:
            if key.endswith(".alpha"):
                pairs.setdefault(key[: -len(".alpha")], {})["alpha"] = tensor
    out = {}
    for base, parts in pairs.items():
        if "down" in parts and "up" in parts:
            alpha = parts.get("alpha")
            out[base] = (
                parts["down"],
                parts["up"],
                float(to_numpy(alpha)) if alpha is not None else None,
            )
    return out


def _f32(t: Any):
    """A checkpoint tensor on the default device, widened to float32 there
    (its stored bytes are what crosses to the device)."""
    if not isinstance(t, (np.ndarray, jax.Array)):
        t = to_numpy(t)
    return jnp.asarray(t).astype(jnp.float32)


class _Baked(Mapping):
    """A state dict with LoRA deltas pending: a key the plan names is baked
    when it is TAKEN — ``W + scale · delta`` in float32 on the default device,
    one tensor at a time (a 70 M-element sum is milliseconds there and seconds
    in numpy) — and every other key comes back as the base holds it (stored
    type and all). A converter that walks the keys once (every one here does)
    never holds more than one baked tensor beside its own output."""

    def __init__(self, base: Mapping[str, Any], plan: dict[str, Any]):
        self._base, self._plan = base, plan

    def __getitem__(self, key):
        w = self._base[key]
        delta = self._plan.get(key)
        if delta is None:
            return w
        # Finished before the next is started: dispatch runs ahead of the
        # device, and fifty tensors' float32 temporaries in flight at once
        # are gigabytes (15.87 GB at the peak of a run that did not wait).
        return jax.block_until_ready(_f32(w) + delta())

    def __iter__(self):
        return iter(self._base)

    def __len__(self):
        return len(self._base)


# Where a LoRA file names its bases under the module path of a wrapper the
# checkpoint's own keys do not carry (ComfyUI's ``diffusion_model.`` spelling).
_LORA_WRAPPER_PREFIXES = ("model.diffusion_model.", "diffusion_model.", "transformer.")


def bake_lora(
    state_dict: Mapping[str, Any],
    lora_sd: Mapping[str, Any],
    strength: float = 1.0,
) -> Mapping[str, Any]:
    """Merge LoRA deltas into base weights: ``W += strength · (alpha/r) · up @ down``.

    Returns a new state dict over the same keys in which each touched tensor
    is a float32 array on the default device — widened from whatever type the
    base stores, the delta added in float32 — computed when it is taken
    (``_Baked``); untouched tensors are the base's own. A loader that keeps
    its kernels resident in 16 bits (``resident``) therefore rounds a baked
    kernel once, after the sum.
    Unmatched LoRA keys are logged and skipped (the reference
    prints-and-continues on patch failures, 1002-1004). Matching is by
    base-key prefix with '.weight' appended, tolerating a wrapper's module
    prefix (``diffusion_model.``) and the common ``lora_unet_`` /
    underscore-flattened prefixes by also trying a dot-normalized form.
    """
    keys = list(state_dict)
    present = set(keys)
    by_normalized = {k.replace(".", "_"): k for k in keys}
    unmatched = []
    plan: dict[str, Any] = {}
    for base, (down, up, alpha) in _lora_pairs(lora_sd).items():
        target = None
        bare = next((base[len(p):] for p in _LORA_WRAPPER_PREFIXES
                     if base.startswith(p)), base)
        for cand in (f"{base}.weight", base, f"{bare}.weight", bare):
            if cand in present:
                target = cand
                break
        if target is None:
            # kohya convention flattens dots to underscores and prefixes the module
            # tree root (e.g. lora_unet_double_blocks_0_img_attn_qkv).
            stripped = base
            for prefix in ("lora_unet_", "lora_transformer_", "lora_te1_",
                           "lora_te2_", "lora_te_", "lora_"):
                if stripped.startswith(prefix):
                    stripped = stripped[len(prefix):]
                    break
            key = by_normalized.get(f"{stripped}_weight".replace(".", "_"))
            if key is None:
                key = by_normalized.get(stripped.replace(".", "_"))
            if key is None:
                # Prefixed sub-dicts (a text tower extracted as
                # ``cond_stage_model.transformer.text_model...``): the LoRA
                # base names only the module-tree suffix, so fall back to a
                # unique suffix match. Ambiguity (two towers in one dict)
                # skips — callers bake per tower with pre-filtered LoRA keys.
                want = "_" + f"{stripped}_weight".replace(".", "_")
                hits = [v for k, v in by_normalized.items() if k.endswith(want)]
                key = hits[0] if len(hits) == 1 else None
            target = key
        if target is None:
            unmatched.append(base)
            continue
        rank = int(down.shape[0])
        scale = strength * ((alpha / rank) if alpha is not None else 1.0)
        shape = tuple(state_dict[target].shape)
        if len(shape) == 4:  # conv: (O, I, kH, kW) with 1x1 or kxk lora
            spatial = np.broadcast_shapes(tuple(up.shape[2:]), tuple(down.shape[2:]))
            if (up.shape[0], down.shape[1], *spatial) != shape:
                unmatched.append(base)  # 1x1 lora on kxk conv: no center broadcast
                continue

            def delta(down=down, up=up, scale=scale, rank=rank):
                d, u = _f32(down), _f32(up)
                return scale * jnp.einsum(
                    "or...,ri...->oi...",
                    u.reshape(u.shape[0], rank, *u.shape[2:]),
                    d.reshape(rank, d.shape[1], *d.shape[2:]),
                    precision=jax.lax.Precision.HIGHEST,
                )
        else:
            def delta(down=down, up=up, scale=scale):
                return scale * jnp.matmul(
                    _f32(up), _f32(down), precision=jax.lax.Precision.HIGHEST)
        prev = plan.get(target)
        plan[target] = delta if prev is None else (
            lambda a=prev, b=delta: a() + b())
    if unmatched:
        get_logger().warning(
            "bake_lora: %d LoRA key(s) had no base match and were skipped: %s",
            len(unmatched),
            unmatched[:5],
        )
    return _Baked(state_dict, plan)


# --------------------------------------------------------------------------------------
# FLUX checkpoint map (official BFL layout → models/flux.py param tree)
# --------------------------------------------------------------------------------------


def dense_params(sd: Mapping[str, Any], key: str, operand_dtype: Any = None) -> dict:
    """torch ``{key}.weight``/``.bias`` → flax Dense ``kernel``/``bias``. With
    ``operand_dtype`` (the type the module computes in) the kernel stays in
    its resident type (``resident``); the bias is float32 either way."""
    w = sd[f"{key}.weight"]
    out = {"kernel": linear_kernel(w) if operand_dtype is None
           else resident(w, operand_dtype).T}
    if f"{key}.bias" in sd:
        out["bias"] = to_numpy(sd[f"{key}.bias"])
    return out


def tree_to_jnp(tree: Any) -> Any:
    """Nested dict of numpy arrays → jnp arrays (shared by all converters)."""
    if isinstance(tree, dict):
        return {k: tree_to_jnp(v) for k, v in tree.items()}
    return jnp.asarray(tree)


def flux_depths(keys) -> tuple[int, int]:
    """(double blocks, single blocks) a FLUX-layout file holds, from its key
    names (bare or under ``model.diffusion_model.``): a depth cut of a
    published model — a contiguous block range, one pipeline stage's share —
    loads at the depth it has."""
    def count(kind):
        idx = [int(k.split(f"{kind}.", 1)[1].split(".", 1)[0])
               for k in keys if f"{kind}." in k]
        return 1 + max(idx) if idx else 0

    return count("double_blocks"), count("single_blocks")


def convert_flux_checkpoint(
    state_dict: Mapping[str, Any],
    cfg: FluxConfig,
    lora_sd: Mapping[str, Any] | None = None,
    lora_strength: float = 1.0,
) -> dict:
    """Official FLUX state dict (flux1-dev/schnell layout) → the param pytree of
    ``models.flux.FluxModel``. LoRA, when given, is baked first (992-1004 parity).

    Matmul kernels stay in their resident type (``resident``: bfloat16 from a
    bfloat16 file or under bfloat16 compute); norm scales and biases are
    float32. The file orders a token's 2×2-patch features (c, ph, pw) — BFL's
    ``rearrange(img, "b c (h ph) (w pw) -> b (h w) (c ph pw)")`` — and the
    model's patchify orders them (ph, pw, c): ``img_in``'s input rows and the
    final projection's output columns are permuted here, once."""
    sd = dict(state_dict)
    if lora_sd:
        sd = bake_lora(sd, lora_sd, lora_strength)
    H, D = cfg.num_heads, cfg.head_dim
    dt, f32 = cfg.dtype, jnp.float32
    p: dict[str, Any] = {}

    def dense(key, operand=dt):
        return dense_params(sd, key, operand)

    def embedder(prefix):
        return {"in_layer": dense(f"{prefix}.in_layer"),
                "out_layer": dense(f"{prefix}.out_layer")}

    pp = cfg.patch_size ** 2
    ch = cfg.in_channels // pp

    def patch_order(a, axis):
        """Features (c, ph, pw) → (ph, pw, c) along ``axis``."""
        a = jnp.asarray(a)
        shape = a.shape
        a = a.reshape(shape[:axis] + (ch, pp) + shape[axis + 1:])
        return jnp.swapaxes(a, axis, axis + 1).reshape(shape)

    p["img_in"] = dense("img_in")
    p["img_in"]["kernel"] = patch_order(p["img_in"]["kernel"], 0)
    p["txt_in"] = dense("txt_in")
    p["time_in"] = embedder("time_in")
    p["vector_in"] = embedder("vector_in")
    if cfg.guidance_embed:
        p["guidance_in"] = embedder("guidance_in")

    def qkv(key):
        return {"kernel": qkv_kernel(sd[f"{key}.weight"], H, D, dt),
                "bias": qkv_bias(sd[f"{key}.bias"], H, D)}

    def qk_norm(key):
        return {"query_norm": to_numpy(sd[f"{key}.query_norm.scale"]),
                "key_norm": to_numpy(sd[f"{key}.key_norm.scale"])}

    for i in range(cfg.depth):
        t = f"double_blocks.{i}"
        blk: dict[str, Any] = {}
        for stream in ("img", "txt"):
            # the modulation computes in float32 (flux.Modulation)
            blk[f"{stream}_mod"] = {"lin": dense(f"{t}.{stream}_mod.lin", f32)}
            blk[f"{stream}_attn_qkv"] = qkv(f"{t}.{stream}_attn.qkv")
            blk[f"{stream}_attn_norm"] = qk_norm(f"{t}.{stream}_attn.norm")
            blk[f"{stream}_attn_proj"] = dense(f"{t}.{stream}_attn.proj")
            blk[f"{stream}_mlp_in"] = dense(f"{t}.{stream}_mlp.0")
            blk[f"{stream}_mlp_out"] = dense(f"{t}.{stream}_mlp.2")
        p[f"double_blocks_{i}"] = blk

    for i in range(cfg.depth_single_blocks):
        t = f"single_blocks.{i}"
        p[f"single_blocks_{i}"] = {
            "modulation": {"lin": dense(f"{t}.modulation.lin", f32)},
            "linear1": dense(f"{t}.linear1"),
            "linear2": dense(f"{t}.linear2"),
            "norm": qk_norm(f"{t}.norm"),
        }

    # final_layer.adaLN_modulation.1 emits (shift, scale); our final_mod emits the
    # same two chunks in the same order. Both final layers compute in float32.
    p["final_mod"] = dense("final_layer.adaLN_modulation.1", f32)
    p["final_proj"] = {k: patch_order(v, v.ndim - 1)
                       for k, v in dense("final_layer.linear", f32).items()}

    return tree_to_jnp(p)
