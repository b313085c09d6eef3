"""Checkpoint loading: safetensors file → converted params → ready DiffusionModel.

The reference leaves model loading to its host app and replicates the already-loaded
torch module (SURVEY §5.4); standalone, this framework needs the load path itself:

    model = load_flux_checkpoint("flux1-schnell.safetensors", flux_schnell_config())
    pm = parallelize(model, chain)

Design points:

- **No wasted init.** ``flax.Module.init`` on a FLUX-scale model allocates and
  initializes billions of parameters just to throw them away. The builders here
  construct the module + metadata (block lists, pipeline spec) and attach the
  converted checkpoint params directly.
- LoRA merges *before* conversion (``bake_lora``) — the analogue of the reference's
  bake-before-replicate (any_device_parallel.py:992-1004).
- fp8/bf16-stored tensors upcast on read (93-124/688-699 parity lives in
  convert.to_numpy); safetensors handles the raw dtypes via ml_dtypes.
"""

from __future__ import annotations

import os
from collections.abc import Mapping
from typing import Any

import numpy as np

from ..utils.logging import get_logger
from .api import DiffusionModel
from .convert import bake_lora, convert_flux_checkpoint, flux_depths, to_numpy
from .convert_unet import convert_sd_unet_checkpoint, strip_prefix
from .flux import FluxConfig, build_flux
from .unet import UNetConfig, build_unet
from .wan import WanConfig, build_wan


def params_nbytes(params) -> int:
    """Total stored bytes of a parameter pytree (QuantTensor int8 leaves count
    at their stored width — the number that competes for HBM)."""
    import jax

    return sum(
        int(l.size) * l.dtype.itemsize for l in jax.tree.leaves(params)
    )


def _bytes_by_dtype(params) -> dict[str, int]:
    import jax

    by_dtype: dict[str, int] = {}
    for leaf in jax.tree.leaves(params):
        name = str(leaf.dtype)
        by_dtype[name] = by_dtype.get(name, 0) + int(leaf.size) * leaf.dtype.itemsize
    return by_dtype


def _set_resident_gauges(model: str, by_dtype: dict[str, int], on_chip: bool) -> None:
    from ..utils.metrics import registry

    for name, nbytes in by_dtype.items():
        registry.gauge(
            "pa_params_resident_bytes", nbytes if on_chip else 0,
            labels={"model": model, "dtype": name},
            help="bytes of a loaded model's parameters on the chip, by stored "
                 "type (0 while the residency rule holds the model off the chip)",
        )


def _nested_dicts_of_arrays(tree) -> bool:
    if not isinstance(tree, dict) or not tree:
        return False
    return all(
        _nested_dicts_of_arrays(v) if isinstance(v, dict)
        else hasattr(v, "dtype") and hasattr(v, "shape")
        for v in tree.values()
    )


class ModelOffChip(RuntimeError):
    """Something computed with a model the residency rule had sent off the
    chip, without asking ``residency.ensure(params)`` first."""


class OffChip:
    """What an evicted model's pytree holds where a tensor was: its shape and
    type, and ``ModelOffChip`` for whoever computes with it without asking
    ``residency.ensure`` first — a jitted call (which reads ``weak_type``), a
    conversion to an array, a method."""

    __slots__ = ("model", "shape", "dtype")

    def __init__(self, model: str, shape, dtype):
        self.model, self.shape, self.dtype = model, tuple(shape), np.dtype(dtype)

    ndim = property(lambda self: len(self.shape))
    size = property(lambda self: int(np.prod(self.shape, dtype=np.int64)))

    def __repr__(self) -> str:
        return (f"<{self.model}: a tensor {self.shape} of a model that is off the "
                f"chip (models/loader.Residency); residency.ensure(params) "
                f"brings it back>")

    def __array__(self, *args, **kwargs):
        raise ModelOffChip(repr(self))

    def __getattr__(self, name):
        if name.startswith("__"):
            raise AttributeError(name)  # copy, pickle and numpy's protocols
        raise ModelOffChip(repr(self))


class Residency:
    """Which loaded models are on the chip: ONE rule, in the loader.

    Every loader hands its pytree over through ``record_resident``, which
    admits it here; the loaders that read a file in its stored types (the
    families too large for float32) ask for room BEFORE their first tensor
    lands (``make_room``), and an entry point that knows what its compiled
    program needs beside the parameters asks for that (``ensure(params,
    beside=)``: the video decoder's 6 GB of temporaries). When what is
    resident plus what comes does not fit the budget, the least recently used
    other model that its loader can make again (``reload``: the families read
    from a memory-mapped file in their stored types — a baked LoRA is baked
    again) leaves the chip: its pytree's dicts are rewritten IN PLACE, so
    every object that shares the pytree (a ``dataclasses.replace`` copy, a
    node-cache entry) sees the same thing and the device buffers are freed;
    the leaves are DROPPED, ``OffChip`` placeholders left in their place, and
    nothing is copied (11 GB off the chip in no time). A model without a
    ``reload`` (a float32 family read whole, an in-memory state dict) counts
    and stays. A model comes back by the same rule when a node asks it to
    compute again (``ensure``: the text-encode, sampler and decode entry
    points, and where a pytree is taken to be placed, quantized, merged or
    factored — one dictionary lookup a node, nothing a step).

    ``budget_bytes`` None takes ``devices.memory.usable_hbm_bytes`` of the
    first device (90% of what it reports; ``PA_HBM_BUDGET_BYTES`` overrides),
    which is 0 — no budget, nothing ever moves — on a backend without memory
    statistics. Each move is a ``model-residency`` span (cat ``graph``; model,
    event evict / restore, bytes), counts in
    ``pa_model_residency_total{model,event}`` and is followed by
    ``pa_params_resident_bytes``. Models are held weakly: one the node cache
    lets go of is forgotten the next time the rule looks."""

    def __init__(self, budget_bytes: int | None = None):
        import threading

        self.budget_bytes = budget_bytes
        self._lock = threading.RLock()
        self._entries: dict[int, dict] = {}  # id(params) -> entry; guarded-by: _lock
        self._tick = 0  # guarded-by: _lock

    def budget(self) -> int:
        if self.budget_bytes is None:
            import jax

            from ..devices.memory import usable_hbm_bytes

            self.budget_bytes = usable_hbm_bytes(jax.devices()[0])
        return self.budget_bytes

    def _live(self) -> dict[int, dict]:  # palint: holds _lock
        """The entries whose model is still alive (the others go)."""
        for key, entry in list(self._entries.items()):
            if entry["holder"]() is None:
                del self._entries[key]
        return self._entries

    def resident_bytes(self) -> int:
        with self._lock:
            return sum(e["bytes"] for e in self._live().values() if e["on_chip"])

    def _touch(self, entry: dict) -> None:  # palint: holds _lock
        self._tick += 1
        entry["tick"] = self._tick

    def admit(self, model: str, holder, reload=None) -> None:
        """A loader's model (anything with a ``params`` pytree of nested
        dicts), on the chip: registered as most recently used, after room was
        made beside it. ``reload`` () → the same pytree made again from its
        source; without one the model counts and never moves."""
        import weakref

        params = getattr(holder, "params", None)
        if not _nested_dicts_of_arrays(params):
            return  # nothing to rewrite in place (quantized leaves, frozen
            # containers): left alone, never moved
        by_dtype = _bytes_by_dtype(params)
        with self._lock:
            entry = {"model": model, "bytes": sum(by_dtype.values()),
                     "by_dtype": by_dtype, "on_chip": True, "tick": 0,
                     "reload": reload, "holder": weakref.ref(holder)}
            self._entries[id(params)] = entry
            self._touch(entry)
            self.make_room(0, keep=entry)

    def make_room(self, incoming: int, keep: dict | None = None) -> None:
        """Evict least recently used models until ``incoming`` more bytes fit
        beside what is resident (or nothing else is left to evict)."""
        budget = self.budget()
        if not budget:
            return
        with self._lock:
            while self.resident_bytes() + incoming > budget:
                others = [e for e in self._entries.values()
                          if e["on_chip"] and e["reload"] and e is not keep]
                if not others:
                    return
                self._move(min(others, key=lambda e: e["tick"]), on_chip=False)

    def ensure(self, params, beside: int = 0) -> None:
        """A node is about to compute with this pytree: mark it used, bring it
        back if it was sent out, and make room for the ``beside`` bytes its
        program needs on top (compiled temporaries, where the entry point
        knows them)."""
        with self._lock:
            entry = self._live().get(id(params))
            if entry is not None:
                self._touch(entry)
                if not entry["on_chip"]:
                    self.make_room(entry["bytes"] + beside, keep=entry)
                    self._move(entry, on_chip=True)
                    return
            if beside:
                self.make_room(beside, keep=entry)

    def _move(self, entry: dict, on_chip: bool) -> None:  # palint: holds _lock
        import time

        import jax

        from ..utils import tracing
        from ..utils.metrics import registry

        holder = entry["holder"]()
        if holder is None:  # let go of since the rule last looked
            self._live()
            return
        event = "restore" if on_chip else "evict"
        t0 = time.perf_counter()
        with tracing.span("model-residency", cat="graph", model=entry["model"],
                          event=event, bytes=entry["bytes"]):

            def rewrite(tree: dict, fresh) -> None:
                for key, value in tree.items():
                    if isinstance(value, dict):
                        rewrite(value, fresh and fresh[key])
                    elif on_chip:
                        tree[key] = jax.numpy.asarray(fresh[key])
                    else:
                        tree[key] = OffChip(entry["model"], value.shape, value.dtype)

            rewrite(holder.params, entry["reload"]() if on_chip else None)
            if on_chip:
                jax.block_until_ready(holder.params)
        entry["on_chip"] = on_chip
        registry.counter(
            "pa_model_residency_total",
            labels={"model": entry["model"], "event": event},
            help="models moved on and off the chip by the loader's residency "
                 "rule (models/loader.Residency)",
        )
        _set_resident_gauges(entry["model"], entry["by_dtype"], on_chip)
        get_logger().info(
            "model residency: %s %s (%.2f GB, %.1f s)", event, entry["model"],
            entry["bytes"] / 1e9, time.perf_counter() - t0)


residency = Residency()

# What this checkout's loaders state of themselves, by name, for a harness
# that has to ask before it spends minutes on a configuration they could not
# serve (benchmark/yardstick/reference_wan.py): a WAN file loads at the depth
# it has and in the types it stores (``load_wan_checkpoint``), and a model
# that does not fit beside the others leaves the chip (``Residency``);
# ``qwen-image``: the double-stream family at the depth its file has and its
# Qwen2.5-VL tower load (``load_qwen_image_checkpoint``,
# ``load_qwen25vl_checkpoint``) and a one-frame latent decodes through the
# video autoencoder.
CAPABILITIES = frozenset({"wan-depth-from-file", "residency", "qwen-image"})


def record_resident(model: str, holder, reload=None) -> None:
    """Where a loader hands a model over: ``pa_params_resident_bytes{model=,
    dtype=}`` says what its pytree keeps on the chip, by stored type — the
    load policy (``convert.resident``) readable on a server's ``/metrics``
    without a trace — and the residency rule admits it (``Residency``;
    ``reload``: how to make the pytree again from its memory-mapped file)."""
    _set_resident_gauges(model, _bytes_by_dtype(holder.params), True)
    residency.admit(model, holder, reload)


def pin_params_host(params, device=None):
    """Host-resident placement for the weight-streaming executor
    (parallel/streaming.py): every leaf lands in the device's ``pinned_host``
    memory space where the backend supports memory kinds (TPU — DMA-able
    pages, so the per-stage host→HBM prefetch runs at full PCIe/ICI rate
    without a bounce copy), and falls back to plain host numpy arrays
    otherwise (CPU backend, older runtimes). Either way the returned pytree
    holds NO device-memory footprint — stage sub-pytrees are carved from it
    and streamed per call."""
    import jax

    from ..parallel.mesh import streamed_tree_put

    dev = device if device is not None else jax.devices()[0]
    try:
        kinds = {m.kind for m in dev.addressable_memories()}
    except Exception:  # backend without memory kinds
        kinds = set()
    if "pinned_host" not in kinds:
        if dev.platform == "tpu":
            raise RuntimeError(
                f"{dev} reports no pinned_host memory space ({sorted(kinds)}); "
                f"weight streaming on a TPU needs it"
            )
        get_logger().info(
            "pinned_host memory kind unavailable on %s; keeping weights as "
            "host numpy arrays", dev.platform,
        )
        return jax.tree.map(np.asarray, params)
    sharding = jax.sharding.SingleDeviceSharding(dev, memory_kind="pinned_host")
    return streamed_tree_put(params, lambda _: sharding)


def carve_ranges(sizes: "list[int] | tuple[int, ...]",
                 max_stage_bytes: int | None = None,
                 n_stages: int | None = None) -> list[tuple[int, int]]:
    """The pure carve arithmetic behind :func:`carve_stages`, over segment
    byte sizes alone (no params pytree, no jax) — shared with the
    auto-parallel planner (parallel/planner.py), whose stream-carve
    candidates are exactly this function at different caps/counts. Greedy
    contiguous packing: each stage's bytes fit ``max_stage_bytes`` (half the
    double-buffer budget), or — when only a stage COUNT is given — stages
    are balanced by bytes. Single-segment stages may exceed the byte cap (a
    segment is the atomic streaming unit — the cap then simply degrades to
    one-segment-at-a-time streaming)."""
    sizes = list(sizes)
    total = sum(sizes)
    if max_stage_bytes is None:
        n = max(1, min(len(sizes), int(n_stages or 4)))
        max_stage_bytes = max(1, -(-total // n))
    ranges: list[tuple[int, int]] = []
    start, acc = 0, 0
    for i, sz in enumerate(sizes):
        if i > start and acc + sz > max_stage_bytes:
            ranges.append((start, i))
            start, acc = i, 0
        acc += sz
    ranges.append((start, len(sizes)))
    return ranges


def segment_nbytes(spec, params) -> list[int]:
    """Per-segment parameter bytes of a ``PipelineSpec`` — the byte profile
    the carve (and the planner's stage-carve search) operates on."""
    return [
        params_nbytes({k: params[k] for k in seg.param_keys})
        for seg in spec.segments
    ]


def carve_stages(spec, params, max_stage_bytes: int | None = None,
                 n_stages: int | None = None) -> list[tuple[int, int]]:
    """Partition a ``PipelineSpec``'s segments into contiguous stage ranges
    for the streaming executor: each stage's parameter sub-pytree fits
    ``max_stage_bytes`` (half the double-buffer budget), or — when only a
    stage COUNT is given — stages are balanced by bytes. Returns
    ``[(start, end), ...]`` over ``spec.segments``; see :func:`carve_ranges`
    for the oversized-single-segment caveat."""
    return carve_ranges(
        segment_nbytes(spec, params),
        max_stage_bytes=max_stage_bytes, n_stages=n_stages,
    )


def load_safetensors(path: str | os.PathLike) -> dict[str, np.ndarray]:
    """Read every tensor of a .safetensors file into float32 numpy.

    bf16/f16/fp8-stored tensors upcast here (the conversion dtype policy); the
    model's compute dtype re-casts at apply time.
    """
    from safetensors import safe_open

    out: dict[str, np.ndarray] = {}
    with safe_open(os.fspath(path), framework="numpy") as f:
        for key in f.keys():
            t = f.get_tensor(key)
            out[key] = np.asarray(t, dtype=np.float32) if t.dtype != np.float32 else t
    return out


_SAFETENSORS_DTYPES = {
    "F64": "float64", "F32": "float32", "F16": "float16", "BF16": "bfloat16",
    "F8_E4M3": "float8_e4m3fn", "F8_E5M2": "float8_e5m2",
    "I64": "int64", "I32": "int32", "I16": "int16", "I8": "int8",
    "U8": "uint8", "BOOL": "bool",
}


def open_safetensors(path: str | os.PathLike) -> dict[str, np.ndarray]:
    """Every tensor of a .safetensors file as a read-only view over a memory
    map, in its STORED type: nothing is read until a tensor is used, and
    nothing is widened. The load path of the families too large to pass
    through float32 (``load_flux_checkpoint``, ``load_t5_checkpoint``), whose
    converters take each tensor to its resident type one at a time."""
    import json
    import struct

    import ml_dtypes  # numpy's bfloat16 / fp8; a dependency of jax

    with open(os.fspath(path), "rb") as f:
        (n,) = struct.unpack("<Q", f.read(8))
        header = json.loads(f.read(n))
    data = np.memmap(os.fspath(path), dtype=np.uint8, mode="r", offset=8 + n)
    out: dict[str, np.ndarray] = {}
    for key, meta in header.items():
        if key == "__metadata__":
            continue
        name = _SAFETENSORS_DTYPES[meta["dtype"]]
        dtype = np.dtype(getattr(ml_dtypes, name, None) or name)
        a, b = meta["data_offsets"]
        out[key] = data[a:b].view(dtype).reshape(meta["shape"])
    return out


def _resolve_state_dict(src: Any, stored: bool = False) -> dict[str, Any]:
    """Accept a path to .safetensors or an in-memory {name: tensor} mapping.
    ``stored``: a file's tensors in their stored types (``open_safetensors``)
    instead of upcast to float32."""
    if isinstance(src, (str, os.PathLike)):
        src = open_safetensors(src) if stored else load_safetensors(src)
    if isinstance(src, Mapping):
        if stored:
            # A family too large for float32 is about to become resident at
            # about its stored size: the one check a load makes for room.
            residency.make_room(
                sum(int(getattr(v, "nbytes", 0)) for v in src.values()))
        return dict(src)
    raise TypeError(f"expected a path or state dict, got {type(src).__name__}")


def peek_safetensors(path: str | os.PathLike) -> dict[str, Any]:
    """Key → shape-only stub for every tensor in a .safetensors file, WITHOUT
    reading tensor data (header metadata only). Enough for
    ``sniff_model_family``; a multi-GB checkpoint costs one header read."""
    import types

    from safetensors import safe_open

    with safe_open(os.fspath(path), framework="numpy") as f:
        return {
            k: types.SimpleNamespace(shape=tuple(f.get_slice(k).get_shape()))
            for k in f.keys()
        }


def load_safetensors_subset(
    path: str | os.PathLike, *prefixes: str
) -> dict[str, np.ndarray]:
    """Read only the keys under the given prefixes (e.g. the bundled
    ``cond_stage_model.`` text tower) — the rest of the file is never
    materialized."""
    from safetensors import safe_open

    out: dict[str, np.ndarray] = {}
    with safe_open(os.fspath(path), framework="numpy") as f:
        for key in f.keys():
            if any(key.startswith(p) for p in prefixes):
                t = f.get_tensor(key)
                out[key] = (
                    np.asarray(t, dtype=np.float32)
                    if t.dtype != np.float32 else t
                )
    return out


def _maybe_bake(sd: dict, lora: Any, strength: float) -> dict:
    """Bake one LoRA — or a STACK: ``lora`` may be a list of ``(lora, strength)``
    pairs, applied in order (the stock LoraLoader chain; each shim link appends
    to the list and the whole stack re-bakes from the source checkpoint)."""
    if lora is None:
        return sd
    stack = lora if isinstance(lora, (list, tuple)) else [(lora, strength)]
    for item in stack:
        src_i, s_i = item if isinstance(item, (list, tuple)) else (item, strength)
        lora_sd = _resolve_state_dict(src_i)
        get_logger().info(
            "baking LoRA (%d tensors, strength %.2f)", len(lora_sd), s_i
        )
        sd = bake_lora(sd, lora_sd, s_i)
    return sd


def load_flux_checkpoint(
    src: Any,
    cfg: FluxConfig,
    lora: Any = None,
    lora_strength: float = 1.0,
    name: str = "flux",
) -> DiffusionModel:
    """FLUX checkpoint (path or state dict, official BFL layout) → DiffusionModel.

    A file is read in its stored types and each kernel taken to its resident
    type on its own (``convert.resident``): the 12 B-parameter family is never
    whole in float32. (A LoRA still bakes in float32 on the host.) The block
    counts are facts of the file: a depth cut of a published model loads at
    the depth it has, whatever ``cfg`` says."""
    sd = _resolve_state_dict(src, stored=lora is None)
    depths = flux_depths(sd)
    if 0 not in depths and depths != (cfg.depth, cfg.depth_single_blocks):
        import dataclasses

        get_logger().info(
            "aligning FLUX config to checkpoint: %d double + %d single blocks",
            *depths,
        )
        cfg = dataclasses.replace(
            cfg, depth=depths[0], depth_single_blocks=depths[1]
        )
    sd = _maybe_bake(sd, lora, lora_strength)

    def build():
        return convert_flux_checkpoint(sd, cfg)

    model = build_flux(cfg, name=name, params=build())
    record_resident(name, model, build if lora is None else None)
    return model


def load_zimage_checkpoint(src: Any, cfg=None, name: str = "zimage-turbo") -> DiffusionModel:
    """Z-Image checkpoint (path or state dict, the published ``transformer/``
    key spelling) → DiffusionModel. Read in its stored types, kernel by
    kernel (``convert.resident``), like FLUX: never whole in float32. The
    main-layer and refiner counts are facts of the file: a depth cut of the
    published model loads at the depth it has, whatever ``cfg`` says."""
    import dataclasses

    from .convert_zimage import convert_zimage_checkpoint, zimage_depths
    from .zimage import build_zimage, zimage_turbo_config

    sd = _resolve_state_dict(src, stored=True)
    if cfg is None:
        cfg = zimage_turbo_config()
    layers, refiners = zimage_depths(sd)
    if (layers, refiners) != (cfg.n_layers, cfg.n_refiner_layers):
        get_logger().info(
            "aligning Z-Image config to checkpoint: %d layers, %d + %d refiner "
            "layers", layers, refiners, refiners,
        )
        cfg = dataclasses.replace(cfg, n_layers=layers, n_refiner_layers=refiners)

    def build():
        return convert_zimage_checkpoint(sd, cfg)

    model = build_zimage(cfg, name=name, params=build())
    record_resident(name, model, build)
    return model


def load_qwen_image_checkpoint(src: Any, cfg=None, lora: Any = None,
                               lora_strength: float = 1.0,
                               name: str = "qwen-image") -> DiffusionModel:
    """Qwen-Image checkpoint (path or state dict, the published
    ``transformer/`` key spelling) → DiffusionModel. Read in its stored types,
    kernel by kernel (``convert.resident``), a LoRA's delta added to a kernel
    as it is taken (``convert.bake_lora``), like WAN: never whole in float32.
    The block count is a fact of the file: a depth cut of the published model
    loads at the depth it has, whatever ``cfg`` says."""
    import dataclasses

    from .convert_qwen_image import convert_qwen_image_checkpoint, qwen_image_depth
    from .qwen_image import build_qwen_image, qwen_image_config

    sd = _resolve_state_dict(src, stored=True)
    if cfg is None:
        cfg = qwen_image_config()
    depth = qwen_image_depth(sd)
    if depth and depth != cfg.depth:
        get_logger().info("aligning Qwen-Image config to checkpoint: %d blocks", depth)
        cfg = dataclasses.replace(cfg, depth=depth)

    def build():
        return convert_qwen_image_checkpoint(_maybe_bake(sd, lora, lora_strength), cfg)

    model = build_qwen_image(cfg, name=name, params=build())
    record_resident(name, model, build)
    return model


def load_sd_unet_checkpoint(
    src: Any,
    cfg: UNetConfig,
    lora: Any = None,
    lora_strength: float = 1.0,
    name: str = "sd-unet",
) -> DiffusionModel:
    """SD1.5/SDXL checkpoint → DiffusionModel. Accepts full ComfyUI checkpoints
    (``model.diffusion_model.*`` subtree selected automatically) or bare UNet dicts."""
    sd = strip_prefix(_resolve_state_dict(src))
    sd = _maybe_bake(sd, lora, lora_strength)
    model = build_unet(cfg, name=name, params=convert_sd_unet_checkpoint(sd, cfg))
    record_resident(name, model)
    return model


def load_controlnet_checkpoint(
    src: Any,
    cfg: "UNetConfig | None" = None,
    name: str = "controlnet",
) -> DiffusionModel:
    """ControlNet checkpoint (ldm single-file layout — bare keys or the
    ``control_model.`` prefix some exports carry — or the diffusers
    ``ControlNetModel`` layout most public SDXL controlnets ship in, detected
    by its ``controlnet_cond_embedding.*`` keys and remapped) → a ControlNet
    DiffusionModel for ``apply_control``. With ``cfg=None`` the base-UNet
    family is sniffed off the cross-attention context width (768 → sd15,
    1024 → sd21, 2048/label_emb → sdxl). Loading either layout is host
    behavior the reference assumes (its unwrap, any_device_parallel.py:921-930,
    is agnostic to how the control model got into the MODEL it wraps)."""
    from .controlnet import build_controlnet
    from .convert_unet import (
        convert_controlnet_checkpoint,
        diffusers_controlnet_to_ldm,
    )

    sd = dict(_resolve_state_dict(src))
    if any(k.startswith("control_model.") for k in sd):
        sd = strip_prefix(sd, "control_model.")
    if any(k.startswith("controlnet_cond_embedding.") for k in sd):
        sd = diffusers_controlnet_to_ldm(sd)
    if cfg is None:
        # Package-level attrs (not .unet directly): the node layer resolves
        # configs through the package namespace everywhere else, and tests
        # shrink models by monkeypatching exactly these names.
        from . import sd15_config, sd21_config, sdxl_config

        key = next(
            (k for k in sd if k.endswith("attn2.to_k.weight")
             and k.startswith("input_blocks.")), None,
        )
        ctx = int(to_numpy(sd[key]).shape[1]) if key else 768
        if any(k.startswith("label_emb.") for k in sd) or ctx == 2048:
            cfg = sdxl_config()
        elif ctx == 1024:
            cfg = sd21_config()
        else:
            cfg = sd15_config()
    return build_controlnet(
        cfg, name=name, params=convert_controlnet_checkpoint(sd, cfg)
    )


def sniff_model_family(state_dict: Mapping[str, Any]) -> str:
    """Model family id (nodes._MODEL_FAMILIES vocabulary) from checkpoint key
    signatures — the stock ``CheckpointLoaderSimple`` has no family widget, so
    the compat shim (nodes_compat.py) sniffs it off the file the way the host
    loader the reference defers to does. Keys may be bare or under the full
    checkpoint's ``model.diffusion_model.`` prefix."""
    pfx = ("model.diffusion_model.", "transformer.")
    names = {next((k[len(p):] for p in pfx if k.startswith(p)), k): k
             for k in state_dict}

    def has(prefix: str) -> bool:
        return any(n.startswith(prefix) for n in names)

    def dim(name: str, axis: int) -> int | None:
        key = names.get(name)
        if key is None:
            return None
        shape = getattr(state_dict[key], "shape", None)
        return None if shape is None else int(shape[axis])

    if has("transformer_blocks.") and has("txt_norm.") and any(
            ".attn.add_q_proj." in n for n in names):
        # Qwen-Image's double-stream transformer in its published key
        # spelling (a ``transformer.`` wrapper tolerated), at whatever depth.
        return "qwen-image"
    if has("noise_refiner.") and has("context_refiner.") and has("cap_embedder."):
        # Z-Image's single-stream transformer in its published key spelling:
        # two refiner stacks and a caption embedder beside ``layers``.
        return "zimage-turbo"
    if has("double_blocks."):
        # A FLUX-layout file without a guidance embedder is schnell's, at
        # whatever depth it has (a depth cut is a contiguous block range).
        return "flux-dev" if has("guidance_in.") else "flux-schnell"
    if has("joint_blocks."):
        if any(".x_block.attn2." in n for n in names):
            return "sd35-medium"  # dual-attention mmdit-x
        depth = 1 + max(
            int(n.split(".")[1]) for n in names if n.startswith("joint_blocks.")
        )
        return "sd35-large" if depth >= 38 else "sd3-medium"
    if has("blocks.0.self_attn.") or has("blocks.0.cross_attn."):
        width = dim("blocks.0.self_attn.q.weight", 0)
        return "wan-14b" if width is not None and width >= 5120 else "wan-1.3b"
    if has("input_blocks."):
        # 9 input channels (latent 4 + mask 1 + masked-image latent 4) mark
        # the dedicated inpainting variants of the SD families.
        in_ch = dim("input_blocks.0.0.weight", 1)
        inpaint = "-inpaint" if in_ch == 9 else ""
        if has("label_emb."):
            # SD2.1-unCLIP also carries an adm label_emb, but keeps the SD2
            # block layout (a transformer at input_blocks.1 with OpenCLIP-H
            # 1024-wide context; SDXL's first attention sits deeper and its
            # context is 2048; the SDXL REFINER's sits deeper still and is
            # OpenCLIP-G-only, 1280-wide).
            ctx = dim("input_blocks.1.1.transformer_blocks.0.attn2.to_k.weight", 1)
            if ctx == 1024:
                return "sd21-unclip"
            first_attn = next(
                (n for n in sorted(names)
                 if n.endswith("transformer_blocks.0.attn2.to_k.weight")
                 and n.startswith("input_blocks.")), None,
            )
            if first_attn is not None and dim(first_attn, 1) == 1280:
                return "sdxl-refiner"
            return "sdxl" + inpaint
        ctx = dim("input_blocks.1.1.transformer_blocks.0.attn2.to_k.weight", 1)
        # 768 = CLIP-L (SD1.x); 1024 = OpenCLIP-H (SD2.x). eps-vs-v prediction
        # is not recorded in weights, so SD2.x defaults to the eps preset —
        # pass family explicitly (TPUCheckpointLoader) for v-prediction models.
        if ctx == 768 and inpaint:
            return "sd15-inpaint"
        if ctx == 1024 and inpaint:
            return "sd21-inpaint"
        if inpaint:
            raise ValueError(
                "9-channel (inpainting) checkpoint with an unrecognized "
                f"context width {ctx} — supported inpaint families: "
                "sd15-inpaint, sd21-inpaint, sdxl-inpaint"
            )
        if ctx == 1024:
            # The most common SD2.1 checkpoint (768-v) is v-prediction; with
            # the eps preset it silently produces garbage images. Make the
            # default diagnosable at load time instead of debuggable at
            # render time.
            get_logger().warning(
                "SD2.x checkpoint sniffed as 'sd21' (eps-prediction). If this "
                "is a v-prediction model (e.g. the common 768-v checkpoint), "
                "pass family='sd21-v' via TPUCheckpointLoader or images will "
                "be garbage."
            )
            return "sd21"
        return "sd15"
    raise ValueError(
        "cannot sniff model family: no known diffusion-model key signature "
        "(transformer_blocks/noise_refiner/double_blocks/joint_blocks/self_attn/"
        "input_blocks) in "
        "checkpoint"
    )


def sniff_vae_config(state_dict: Mapping[str, Any]):
    """Pick a VAE family config from checkpoint weights: ``flux_vae_config()`` for a
    16-channel latent, ``sd_vae_config()`` for 4 channels (read off
    ``decoder.conv_in``, prefixed layouts handled). SD1.5 vs SDXL VAEs are
    weight-shape identical but need different scaling factors — the 4-channel default
    warns and SDXL users should pass ``sdxl_vae_config()`` explicitly."""
    from .convert_vae import strip_vae_prefix
    from .vae import flux_vae_config, sd_vae_config

    sd = strip_vae_prefix(state_dict)  # single owner of the prefix vocabulary
    if "decoder.conv_in.weight" not in sd:
        raise KeyError("decoder.conv_in.weight not found — not an AutoencoderKL dict")
    conv_in = to_numpy(sd["decoder.conv_in.weight"])
    z_ch = conv_in.shape[1] if conv_in.ndim == 4 else conv_in.shape[-1]
    if z_ch == 16:
        return flux_vae_config()
    get_logger().warning(
        "4-channel VAE: defaulting to sd_vae_config() (scaling 0.18215); "
        "SDXL VAEs are shape-identical but need sdxl_vae_config() "
        "(scaling 0.13025) — pass cfg= explicitly for SDXL"
    )
    return sd_vae_config()


def load_vae_checkpoint(
    src: Any,
    cfg: "VAEConfig | None" = None,
):
    """AutoencoderKL checkpoint → VAE. Accepts a standalone vae/ae.safetensors, a
    full ComfyUI checkpoint (``first_stage_model.*`` selected automatically), or an
    in-memory state dict. ``cfg`` defaults via ``sniff_vae_config`` (prefer passing
    it explicitly for SDXL)."""
    from .convert_vae import convert_vae_checkpoint
    from .vae import build_vae

    sd = _resolve_state_dict(src)
    if cfg is None:
        cfg = sniff_vae_config(sd)
    # convert_vae_checkpoint owns the prefix strip — no pre-strip here.
    vae = build_vae(cfg, params=convert_vae_checkpoint(sd, cfg))
    record_resident("vae", vae)
    return vae


def load_clip_text_checkpoint(src: Any, cfg=None, open_clip: bool = False):
    """CLIP text tower checkpoint → TextEncoder. ``open_clip=True`` selects the
    OpenCLIP resblocks layout (SDXL's second encoder); default is the HF
    ``text_model.*`` layout (SD1.5 / SDXL first encoder / FLUX clip_l)."""
    from .convert_text import (
        convert_clip_text_checkpoint,
        convert_open_clip_checkpoint,
    )
    from .text_encoders import build_clip_text, clip_l_config, open_clip_g_config

    sd = _resolve_state_dict(src)
    if cfg is None:
        cfg = open_clip_g_config() if open_clip else clip_l_config()
    convert = convert_open_clip_checkpoint if open_clip else convert_clip_text_checkpoint
    enc = build_clip_text(cfg, params=convert(sd, cfg))
    record_resident("open-clip" if open_clip else "clip-text", enc)
    return enc


def load_t5_checkpoint(src: Any, cfg=None):
    """T5 encoder checkpoint (HF layout) → TextEncoder (FLUX/WAN t5xxl). A
    file is read in its stored types, kernel by kernel (``convert.resident``):
    T5-XXL's 4.76 B parameters are 19 GB in float32 and never exist so."""
    from .convert_text import convert_t5_checkpoint
    from .text_encoders import build_t5_encoder, t5_xxl_config

    sd = _resolve_state_dict(src, stored=True)
    if cfg is None:
        cfg = t5_xxl_config()

    def build():
        return convert_t5_checkpoint(sd, cfg)

    enc = build_t5_encoder(cfg, params=build())
    record_resident("t5", enc, build)
    return enc


def load_qwen3_checkpoint(src: Any, cfg=None):
    """Qwen3 checkpoint (HF layout) → TextEncoder (Z-Image's text tower). Read
    in its stored types, kernel by kernel (``convert.resident``): Qwen3-4B is
    16 GB in float32 and never exists so. The last layer and the final norm
    stay in the file: the tower hands on the state before the last layer."""
    from .convert_text import convert_qwen3_checkpoint
    from .text_encoders import build_qwen3, qwen3_4b_config

    sd = _resolve_state_dict(src, stored=True)
    if cfg is None:
        cfg = qwen3_4b_config()

    def build():
        return convert_qwen3_checkpoint(sd, cfg)

    enc = build_qwen3(cfg, params=build())
    record_resident("qwen3", enc, build)
    return enc


def load_qwen25vl_checkpoint(src: Any, cfg=None):
    """Qwen2.5-VL checkpoint (HF layout) → TextEncoder: the language model of
    Qwen-Image's tower, read like Qwen3's. The ``visual.*`` tower and a
    ``lm_head`` stay in the file — text-to-image never runs them — and count
    for nothing: not in the room asked for, not in
    ``pa_params_resident_bytes``."""
    from .convert_text import convert_qwen3_checkpoint
    from .text_encoders import build_qwen3, qwen25_vl_7b_config

    if isinstance(src, (str, os.PathLike)):
        src = open_safetensors(src)
    sd = _resolve_state_dict(
        {k: v for k, v in src.items()
         if not k.startswith(("visual.", "model.visual.", "lm_head."))},
        stored=True)
    if cfg is None:
        cfg = qwen25_vl_7b_config()

    def build():
        return convert_qwen3_checkpoint(sd, cfg)

    enc = build_qwen3(cfg, params=build())
    record_resident("qwen25vl", enc, build)
    return enc


def load_wan_checkpoint(
    src: Any,
    cfg: WanConfig,
    lora: Any = None,
    lora_strength: float = 1.0,
    params_converter=None,
    name: str = "wan",
) -> DiffusionModel:
    """WAN checkpoint → DiffusionModel. The official Wan2.x layout converts via
    ``convert_wan_checkpoint`` by default (with ``lora`` baked before
    conversion, like the other families); pass ``params_converter``
    (state_dict, cfg) -> params for repacked layouts, or a pre-converted param
    pytree as ``src`` (lora is not supported for pre-converted pytrees).

    A file is read in its stored types and each kernel taken to its resident
    type on its own (``convert.resident``), a LoRA's delta added to a kernel
    as it is taken (``convert.bake_lora``): an expert is never whole in
    float32. The block count is a fact of the file: a depth cut of a
    published model loads at the depth it has, whatever ``cfg`` says."""
    import jax

    build = None
    if params_converter is not None:
        params = params_converter(
            _maybe_bake(dict(_resolve_state_dict(src)), lora, lora_strength), cfg
        )
    elif isinstance(src, Mapping) and not any("." in k for k in src):
        if lora is not None:
            raise ValueError(
                "lora baking needs the flat checkpoint layout; pass the "
                "state dict / file instead of a pre-converted param pytree"
            )
        # Pre-converted nested pytree: apply the float32 upcast policy to every
        # leaf (bf16/fp8 storage dtypes included), same as the file-load path.
        params = jax.tree.map(to_numpy, src)
    else:
        import dataclasses

        from .convert_wan import convert_wan_checkpoint, wan_depth

        sd = _resolve_state_dict(src, stored=True)
        depth = wan_depth(sd)
        if depth and depth != cfg.depth:
            get_logger().info(
                "aligning WAN config to checkpoint: %d blocks", depth)
            cfg = dataclasses.replace(cfg, depth=depth)

        def build(cfg=cfg):
            return convert_wan_checkpoint(_maybe_bake(sd, lora, lora_strength), cfg)

        try:
            params = build()
        except KeyError as e:
            raise ValueError(
                f"state dict is not the official Wan2.x layout (missing {e}); "
                "pass params_converter=(state_dict, cfg) -> params for repacked "
                "layouts, or a pre-converted param pytree"
            ) from e
    model = build_wan(cfg, name=name, params=params)
    record_resident(name, model, build)
    return model


def load_wan_vae_checkpoint(src: Any, cfg=None):
    """WAN video-VAE checkpoint (official Wan2.x_VAE layout, optionally wrapped
    under a ``vae.``/``first_stage_model.`` prefix) → VideoVAE."""
    from .convert_wan_vae import convert_wan_vae_checkpoint
    from .video_vae import build_video_vae, wan_vae_config

    sd = _resolve_state_dict(src)
    for prefix in ("vae.", "first_stage_model.", "model."):
        stripped = {
            k[len(prefix):]: v for k, v in sd.items() if k.startswith(prefix)
        }
        if any(k.startswith("encoder.conv1.") for k in stripped):
            sd = stripped
            break
    if cfg is None:
        cfg = wan_vae_config()
    try:
        params = convert_wan_vae_checkpoint(sd, cfg)
    except KeyError as e:
        raise ValueError(
            f"state dict is not the official Wan2.x VAE layout (missing {e})"
        ) from e
    vae = build_video_vae(cfg, params=params)
    record_resident("video-vae", vae)
    return vae


def load_mmdit_checkpoint(src: Any, cfg, lora: Any = None,
                          lora_strength: float = 1.0, name: str = "mmdit"):
    """SD3/SD3.5 MMDiT checkpoint (SAI/ComfyUI single-file, optionally under
    model.diffusion_model.) → DiffusionModel."""
    from .convert_mmdit import convert_mmdit_checkpoint, strip_mmdit_prefix
    from .mmdit import build_mmdit

    sd = strip_mmdit_prefix(_resolve_state_dict(src))
    sd = _maybe_bake(sd, lora, lora_strength)
    # Dual-attention layout (SD3.5-medium mmdit-x) and q/k RMS norm presence are
    # facts of the checkpoint — align the config to what the state dict actually
    # contains so a caller passing a generic config still loads correctly (the
    # converter itself stays strict on both).
    attn2_layers = tuple(sorted(
        int(k.split(".")[1])
        for k in sd
        if k.startswith("joint_blocks.") and k.endswith(".x_block.attn2.qkv.weight")
    ))
    has_qk_norm = any(
        k.startswith("joint_blocks.") and k.endswith(".attn.ln_q.weight") for k in sd
    )
    if (
        attn2_layers != tuple(cfg.x_block_self_attn_layers)
        or has_qk_norm != cfg.qk_norm
    ):
        import dataclasses

        from ..utils.logging import get_logger

        get_logger().info(
            "aligning MMDiT config to checkpoint: dual-attention layers %s, "
            "qk_norm=%s", list(attn2_layers), has_qk_norm,
        )
        cfg = dataclasses.replace(
            cfg, x_block_self_attn_layers=attn2_layers, qk_norm=has_qk_norm
        )
    model = build_mmdit(cfg, name=name, params=convert_mmdit_checkpoint(sd, cfg))
    record_resident(name, model)
    return model
