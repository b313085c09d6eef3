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


def record_resident(model: str, params) -> None:
    """``pa_params_resident_bytes{model=,dtype=}``: what a loaded pytree keeps
    resident, by stored type, set once where a loader hands the pytree over —
    the load policy (``convert.resident``) readable on a server's ``/metrics``
    without a trace."""
    import jax

    from ..utils.metrics import registry

    by_dtype: dict[str, int] = {}
    for leaf in jax.tree.leaves(params):
        name = str(leaf.dtype)
        by_dtype[name] = by_dtype.get(name, 0) + int(leaf.size) * leaf.dtype.itemsize
    for name, nbytes in by_dtype.items():
        registry.gauge(
            "pa_params_resident_bytes", nbytes,
            labels={"model": model, "dtype": name},
            help="bytes of a loaded model's parameters by stored type",
        )


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
        return open_safetensors(src) if stored else load_safetensors(src)
    if isinstance(src, Mapping):
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
    model = build_flux(cfg, name=name, params=convert_flux_checkpoint(sd, cfg))
    record_resident(name, model.params)
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
    model = build_zimage(cfg, name=name, params=convert_zimage_checkpoint(sd, cfg))
    record_resident(name, model.params)
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
    record_resident(name, model.params)
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
    pfx = "model.diffusion_model."
    names = {k[len(pfx):] if k.startswith(pfx) else k: k for k in state_dict}

    def has(prefix: str) -> bool:
        return any(n.startswith(prefix) for n in names)

    def dim(name: str, axis: int) -> int | None:
        key = names.get(name)
        if key is None:
            return None
        shape = getattr(state_dict[key], "shape", None)
        return None if shape is None else int(shape[axis])

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
        "(noise_refiner/double_blocks/joint_blocks/self_attn/input_blocks) in "
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
    record_resident("vae", vae.params)
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
    record_resident("open-clip" if open_clip else "clip-text", enc.params)
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
    enc = build_t5_encoder(cfg, params=convert_t5_checkpoint(sd, cfg))
    record_resident("t5", enc.params)
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
    enc = build_qwen3(cfg, params=convert_qwen3_checkpoint(sd, cfg))
    record_resident("qwen3", enc.params)
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
    pytree as ``src`` (lora is not supported for pre-converted pytrees)."""
    import jax

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
        from .convert_wan import convert_wan_checkpoint

        try:
            params = convert_wan_checkpoint(
                _maybe_bake(dict(_resolve_state_dict(src)), lora, lora_strength),
                cfg,
            )
        except KeyError as e:
            raise ValueError(
                f"state dict is not the official Wan2.x layout (missing {e}); "
                "pass params_converter=(state_dict, cfg) -> params for repacked "
                "layouts, or a pre-converted param pytree"
            ) from e
    return build_wan(cfg, name=name, params=params)


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
    return build_video_vae(cfg, params=params)


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
    record_resident(name, model.params)
    return model
