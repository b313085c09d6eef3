"""The node API layer — ComfyUI-style declarative nodes over the TPU framework.

This re-exposes the reference's entire L4 surface (SURVEY §2a) with the same node
protocol (``INPUT_TYPES`` / ``RETURN_TYPES`` / ``RETURN_NAMES`` / ``FUNCTION`` /
``CATEGORY`` / ``DESCRIPTION``) so a ComfyUI-style graph host can register and drive
the framework exactly as it drives the reference:

- ``ParallelDevice``      — one chain link, chainable (any_device_parallel.py:768-832)
- ``ParallelDeviceList``  — flat 1-4 device/percentage variant (834-882)
- ``ParallelAnything``    — the orchestrator node (884-1471)
- ``NODE_CLASS_MAPPINGS`` / ``NODE_DISPLAY_NAME_MAPPINGS`` (1473-1483)

The DEVICE_CHAIN wire value is the reference's: a plain list of
``{"device": str, "percentage": float, "weight": float}`` dicts (823-832). The
``weight`` key is written for wire parity but never read back — the orchestrator
renormalizes from ``percentage`` only, exactly like setup_parallel (1019-1027, where
the SURVEY flags ``weight`` as dead data).
"""

from __future__ import annotations

from typing import Any

from .devices.discovery import available_devices
from .parallel.chain import DeviceChain
from .parallel.orchestrator import ParallelConfig, parallelize
from .utils import tracing

CATEGORY = "parallel/tpu"

# Stock ComfyUI seed widgets are 64-bit: the UI's "randomize" fills the full
# [0, 2**64) range. jax.random.key takes a SIGNED int64, so a seed >= 2**63
# coming through the stock shims (nodes_compat) would raise OverflowError in
# roughly half of randomly-seeded exported workflows.
SEED_MAX = 2**64 - 1


def seed_key(seed: int):
    """``jax.random.key`` for any ComfyUI seed, folding the stock 64-bit range
    deterministically into jax's signed-int64 domain."""
    import jax

    return jax.random.key(int(seed) % 2**63)


def chain_from_wire(entries: list[dict[str, Any]] | None) -> DeviceChain:
    """DEVICE_CHAIN wire format → DeviceChain (drops pct <= 0, parity 876-882)."""
    if not entries:
        return DeviceChain()
    return DeviceChain.from_pairs(
        (e["device"], float(e.get("percentage", 0.0))) for e in entries
    )


def chain_to_wire(chain: DeviceChain) -> list[dict[str, Any]]:
    """DeviceChain → the reference's wire format, including the dead ``weight`` key
    (pct/100, written at 826/880 and never read)."""
    return [
        {"device": l.device, "percentage": l.percentage, "weight": l.percentage / 100.0}
        for l in chain.links
    ]


class ParallelDevice:
    """One link in the device chain: pick a device + workload %, chainable via the
    optional ``previous_devices`` input (parity: 768-832)."""

    DESCRIPTION = (
        "Add a device to the parallel chain with a workload percentage. "
        "Chain multiple nodes to build an N-device setup."
    )
    RETURN_TYPES = ("DEVICE_CHAIN",)
    RETURN_NAMES = ("device_chain",)
    FUNCTION = "add_device"
    CATEGORY = CATEGORY

    @classmethod
    def get_available_devices(cls) -> list[str]:
        return available_devices()

    @classmethod
    def INPUT_TYPES(cls):
        devices = cls.get_available_devices()
        return {
            "required": {
                "device_id": (
                    devices,
                    {"default": devices[0], "tooltip": "Device to add to the chain"},
                ),
                "percentage": (
                    "FLOAT",
                    {
                        "default": 50.0,
                        "min": 1.0,
                        "max": 100.0,
                        "step": 1.0,
                        "tooltip": "Share of the workload for this device",
                    },
                ),
            },
            "optional": {
                "previous_devices": (
                    "DEVICE_CHAIN",
                    {"tooltip": "Chain from an upstream Parallel Device node"},
                ),
            },
        }

    def add_device(self, device_id: str, percentage: float, previous_devices=None):
        # Copy-then-append, like the reference (821-832) — upstream lists are never
        # mutated, so re-running a graph node is side-effect free.
        chain = list(previous_devices) if previous_devices else []
        chain.append(
            {
                "device": device_id,
                "percentage": float(percentage),
                "weight": float(percentage) / 100.0,
            }
        )
        return (chain,)


class ParallelDeviceList:
    """Flat alternative: one node, four device+percentage pairs; entries with
    percentage <= 0 are dropped (parity: 834-882)."""

    DESCRIPTION = "Configure up to 4 devices in one node; 0% disables a slot."
    RETURN_TYPES = ("DEVICE_CHAIN",)
    RETURN_NAMES = ("device_chain",)
    FUNCTION = "create_list"
    CATEGORY = CATEGORY
    N_SLOTS = 4

    @classmethod
    def get_available_devices(cls) -> list[str]:
        return available_devices()

    @classmethod
    def INPUT_TYPES(cls):
        devices = cls.get_available_devices()
        required = {}
        for i in range(1, cls.N_SLOTS + 1):
            required[f"device_{i}"] = (
                devices,
                {"default": devices[0], "tooltip": f"Device for slot {i}"},
            )
            required[f"percentage_{i}"] = (
                "FLOAT",
                {
                    "default": 50.0 if i <= 2 else 0.0,
                    "min": 0.0,
                    "max": 100.0,
                    "step": 1.0,
                    "tooltip": f"Workload share for slot {i}; 0 disables",
                },
            )
        return {"required": required}

    def create_list(self, **kwargs):
        chain = []
        for i in range(1, self.N_SLOTS + 1):
            pct = float(kwargs.get(f"percentage_{i}", 0.0))
            if pct <= 0:
                continue
            dev = kwargs[f"device_{i}"]
            chain.append({"device": dev, "percentage": pct, "weight": pct / 100.0})
        return (chain,)


class ParallelAnything:
    """The orchestrator node: takes MODEL + DEVICE_CHAIN, wraps the model so every
    sampler step runs parallel over the chain, returns the wrapped MODEL
    (parity: 884-1471)."""

    DESCRIPTION = (
        "True multi-device parallelism: shards each denoise step across the device "
        "chain as one SPMD program (data parallel for batches, pipeline block "
        "placement for batch=1)."
    )
    RETURN_TYPES = ("MODEL",)
    RETURN_NAMES = ("model",)
    FUNCTION = "setup_parallel"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "model": ("MODEL", {"tooltip": "Diffusion model to parallelize"}),
                "parallel_devices": (
                    "DEVICE_CHAIN",
                    {"tooltip": "Device chain from Parallel Device node(s)"},
                ),
                # Widget defaults match the reference's effective values (SURVEY §5.6:
                # the auto_vram_balance widget default True wins over the python
                # signature default False because hosts always pass widget values).
                "workload_split": (
                    "BOOLEAN",
                    {"default": True, "tooltip": "Split batches across devices"},
                ),
                "auto_vram_balance": (
                    "BOOLEAN",
                    {
                        "default": True,
                        "tooltip": "Blend workload split with free device memory",
                    },
                ),
                "purge_cache": (
                    "BOOLEAN",
                    {"default": True, "tooltip": "Release caches at teardown"},
                ),
                "purge_models": (
                    "BOOLEAN",
                    {"default": False, "tooltip": "Also drop compiled programs"},
                ),
            },
        }

    def setup_parallel(
        self,
        model,
        parallel_devices,
        workload_split: bool = True,
        auto_vram_balance: bool = True,
        purge_cache: bool = True,
        purge_models: bool = False,
        **config_extra,
    ):
        chain = chain_from_wire(parallel_devices)
        if not config_extra.get("reactivate_after"):
            # Widget convention: 0 = off. ParallelConfig uses None for off —
            # a literal 0 would mean "reactivate on the very next step".
            config_extra.pop("reactivate_after", None)
        config = ParallelConfig(
            workload_split=workload_split,
            auto_memory_balance=auto_vram_balance,
            purge_cache=purge_cache,
            purge_models=purge_models,
            **config_extra,
        )
        # parallelize returns the model unchanged on an unusable chain, matching the
        # reference's abort paths (1019-1027, 1037-1042).
        return (parallelize(model, chain, config),)


class ParallelAnythingAdvanced(ParallelAnything):
    """The orchestrator node with the beyond-reference knobs exposed: weight
    sharding (FSDP for models bigger than one chip) and tensor parallelism."""

    DESCRIPTION = (
        ParallelAnything.DESCRIPTION
        + " Advanced: FSDP weight sharding and tensor parallelism for models "
        "larger than a single device."
    )
    # setup_parallel's **config_extra already routes the extra widgets into
    # ParallelConfig — no forwarding override needed.
    FUNCTION = "setup_parallel"

    @classmethod
    def INPUT_TYPES(cls):
        base = ParallelAnything.INPUT_TYPES()
        base["required"]["weight_sharding"] = (
            ["replicate", "fsdp"],
            {
                "default": "replicate",
                "tooltip": "fsdp shards each weight across the chain (model > 1 chip)",
            },
        )
        base["required"]["tensor_parallel"] = (
            "INT",
            {
                "default": 1,
                "min": 1,
                "max": 64,
                "tooltip": "model-axis size; >1 partitions the matmuls (GSPMD TP)",
            },
        )
        base["optional"] = dict(base.get("optional") or {})
        base["optional"]["pipeline_microbatches"] = (
            "INT",
            {
                "default": 0,
                "min": 0,
                "max": 64,
                "tooltip": "GPipe-style throughput pipelining for batch>1: "
                           "split the batch into this many microbatches "
                           "streamed through the stage chain (0 or 1 = off; "
                           "needs >=2 to pipeline)",
            },
        )
        base["optional"]["reactivate_after"] = (
            "INT",
            {
                "default": 0,
                "min": 0,
                "max": 10000,
                "tooltip": "auto-resume the parallel path this many single-"
                           "device steps after a step-OOM demotion (0 = "
                           "permanent demotion until manual reactivate)",
            },
        )
        return base


# ---------------------------------------------------------------------------
# Host-layer nodes (beyond the reference's 3 nodes).
#
# The reference assumes ComfyUI provides the rest of the graph —
# CheckpointLoaderSimple → CLIPTextEncode → KSampler → VAEDecode — around its
# wrapped MODEL (SURVEY §2g lists exactly what it consumes from that host).
# Standalone, this framework supplies those surrounding nodes itself, with the
# same wire vocabulary (MODEL / CLIP / CONDITIONING / LATENT / VAE / IMAGE), so a
# reference user's whole workflow maps node-for-node.
# ---------------------------------------------------------------------------

_MODEL_FAMILIES = (
    "sd15", "sd15-inpaint", "sd21", "sd21-v", "sd21-inpaint", "sd21-unclip",
    "sdxl", "sdxl-inpaint", "sdxl-refiner",
    "sd3-medium", "sd35-medium", "sd35-large",
    "flux-dev", "flux-schnell", "zimage-turbo", "qwen-image", "wan-1.3b",
    "wan-14b",
)


class TPUCheckpointLoader:
    """Checkpoint file → (MODEL, VAE). The diffusion subtree and (when present in
    the file) the first_stage_model VAE subtree load together, like the host
    loader the reference defers to."""

    DESCRIPTION = "Load a diffusion checkpoint (and its bundled VAE) for a family."
    RETURN_TYPES = ("MODEL", "VAE")
    RETURN_NAMES = ("model", "vae")
    FUNCTION = "load"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "ckpt_path": ("STRING", {"default": "", "tooltip": "safetensors path"}),
                "family": (
                    list(_MODEL_FAMILIES),
                    {"default": "sd15", "tooltip": "model family / config preset"},
                ),
            },
            "optional": {
                "vae_path": (
                    "STRING",
                    {"default": "", "tooltip": "separate VAE file (flux ae, fixed vae)"},
                ),
                "lora_path": ("STRING", {"default": ""}),
                "lora_strength": ("FLOAT", {"default": 1.0, "min": -4.0, "max": 4.0}),
                "quantize": (
                    ["none", "int8"],
                    {"default": "none",
                     "tooltip": "int8 halves weight HBM (per-channel symmetric; "
                                "e.g. flux-dev fits one v5e chip replicated)"},
                ),
            },
        }

    def load(
        self,
        ckpt_path: str,
        family: str,
        vae_path: str = "",
        lora_path: str = "",
        lora_strength: float = 1.0,
        quantize: str = "none",
        load_vae: bool = True,
    ):
        # load_vae=False skips the VAE conversion and returns (MODEL, None) —
        # for re-load paths that only need the diffusion model (the
        # LoraLoader shim re-bakes and discards everything else).
        from .models import (
            flux_dev_config,
            flux_schnell_config,
            flux_vae_config,
            load_zimage_checkpoint,
            load_flux_checkpoint,
            load_safetensors,
            load_sd_unet_checkpoint,
            load_vae_checkpoint,
            open_safetensors,
            sd15_config,
            sd21_config,
            sd_vae_config,
            sdxl_config,
            sdxl_refiner_config,
            sdxl_vae_config,
        )

        lora = lora_path or None

        import contextlib

        import jax

        # int8 load path: conversion materializes the FULL-precision pytree —
        # on the accelerator that would OOM before quantization can help (the
        # whole point is that flux-dev-class f32 does NOT fit a v5e). Pin the
        # load to host CPU RAM, quantize there, and let placement (parallelize)
        # move only the int8 payload to the chips.
        load_ctx = (
            jax.default_device(jax.devices("cpu")[0])
            if quantize == "int8"
            else contextlib.nullcontext()
        )

        def maybe_quant(m):
            if quantize == "int8":
                from .models import quantize_model

                return quantize_model(m)
            return m

        stored = (family in ("flux-dev", "flux-schnell", "zimage-turbo", "qwen-image")
                  or family.startswith("wan"))
        # The FLUX families, Z-Image, Qwen-Image and WAN are read in the
        # file's stored types and never pass through float32 whole
        # (models/loader).
        sd = (open_safetensors if stored else load_safetensors)(ckpt_path)

        def named_for_the_file() -> str:
            # Two loads of one family (WAN's experts, a LoRA-baked copy beside
            # its base) are two programs' worth of counters and spans
            # (``denoise`` / pa_denoiser_calls_total{program=}).
            import os as _os

            return (_os.path.splitext(_os.path.basename(ckpt_path))[0]
                    + ("+lora" if lora else ""))

        if family == "qwen-image":
            # Qwen-Image: the double-stream denoiser alone — its releases keep
            # the autoencoder (the WAN2.1 architecture on one frame) in a file
            # of its own, as WAN's do. The preset is looked up at call time:
            # tests shrink models by patching the package-level one.
            from . import models as _models

            with load_ctx:
                model = _models.load_qwen_image_checkpoint(
                    sd, _models.qwen_image_config(), lora, lora_strength,
                    name=named_for_the_file())
                model = maybe_quant(model)
            # The host's Qwen-Image sampling settings: the flow table at
            # shift 3.1, what the template's ModelSamplingAuraFlow says.
            model.sampler_prefs = {"shift": 3.1}
            if not load_vae:
                return model, None
            if not vae_path:
                raise ValueError(
                    "Qwen-Image keeps its autoencoder in a file of its own "
                    "(qwen_image_vae): set vae_path to it — or, in a stock "
                    "graph, load the denoiser with UNETLoader and the "
                    "autoencoder with VAELoader"
                )
            return model, _models.load_wan_vae_checkpoint(vae_path)
        if family.startswith("wan"):
            # WAN family: video DiT + causal 3D VAE (its own checkpoint file —
            # WAN releases don't bundle the VAE with the DiT weights).
            from .models import (
                load_wan_checkpoint,
                load_wan_vae_checkpoint,
                wan_1_3b_config,
                wan_14b_config,
            )

            wcfg = (wan_14b_config if family == "wan-14b" else wan_1_3b_config)()
            # Variant sniffing within the family: i2v checkpoints carry extra
            # in-channels (36 = latent + frame mask + cond latent) and the
            # WAN2.1-style ones add the CLIP-vision branch (img_emb.* — its
            # proj.1 Linear's input width is the CLIP hidden size). The depth
            # is a fact of the file too (``load_wan_checkpoint``).
            import dataclasses as _dc

            pe = sd.get("patch_embedding.weight")
            img_w = sd.get("img_emb.proj.1.weight")
            wcfg = _dc.replace(
                wcfg,
                in_channels=(
                    int(pe.shape[1]) if pe is not None else wcfg.in_channels
                ),
                img_dim=(
                    int(img_w.shape[1]) if img_w is not None else None
                ),
            )
            with load_ctx:
                # Named for the FILE: both experts behind one compiled step.
                model = load_wan_checkpoint(
                    sd, wcfg, lora, lora_strength, name=named_for_the_file())
                model = maybe_quant(model)
            if not load_vae:
                return model, None
            if not vae_path:
                raise ValueError(
                    "WAN releases keep the autoencoder in a file of its own "
                    "(Wan2.1_VAE): set vae_path to it (safetensors) — or, in "
                    "a stock graph, load the denoiser with UNETLoader and the "
                    "autoencoder with VAELoader, which need no vae_path"
                )
            return model, load_wan_vae_checkpoint(vae_path)
        with load_ctx:
            if family in ("sd15", "sd15-inpaint"):
                # Kwargs only for the inpaint variant: tests monkeypatch the
                # preset factories with zero-arg tiny versions.
                ucfg = sd15_config(
                    **({"in_channels": 9} if family == "sd15-inpaint" else {})
                )
                model = load_sd_unet_checkpoint(sd, ucfg, lora, lora_strength)
                vae_cfg = sd_vae_config()
            elif family in ("sd3-medium", "sd35-medium", "sd35-large"):
                from .models import (
                    load_mmdit_checkpoint,
                    sd3_medium_config,
                    sd3_vae_config,
                    sd35_large_config,
                    sd35_medium_config,
                )

                mcfg = {
                    "sd35-large": sd35_large_config,
                    "sd35-medium": sd35_medium_config,
                    "sd3-medium": sd3_medium_config,
                }[family]()
                model = load_mmdit_checkpoint(sd, mcfg, lora, lora_strength)
                vae_cfg = sd3_vae_config()
            elif family in ("sd21", "sd21-v", "sd21-inpaint", "sd21-unclip"):
                ucfg = sd21_config(
                    prediction="v" if family == "sd21-v" else "eps",
                    **({"in_channels": 9} if family == "sd21-inpaint" else {}),
                )
                if family == "sd21-unclip":
                    # The unCLIP variants derive from the 768-v model
                    # (v-prediction) and add an adm head whose width the
                    # checkpoint's label_emb records (1536 = ViT-L embeds +
                    # level embedding, 2048 = ViT-H).
                    import dataclasses as _dc

                    le = sd.get("label_emb.0.0.weight")
                    if le is None:
                        le = sd.get("model.diffusion_model.label_emb.0.0.weight")
                    if le is None:
                        raise ValueError(
                            "sd21-unclip checkpoint has no label_emb — "
                            "not an unCLIP variant"
                        )
                    ucfg = _dc.replace(
                        ucfg, prediction="v",
                        adm_in_channels=int(le.shape[1]),
                    )
                model = load_sd_unet_checkpoint(sd, ucfg, lora, lora_strength)
                vae_cfg = sd_vae_config()
            elif family in ("sdxl", "sdxl-inpaint", "sdxl-refiner"):
                if family == "sdxl-refiner":
                    xcfg = sdxl_refiner_config()
                else:
                    xcfg = sdxl_config(
                        **({"in_channels": 9} if family == "sdxl-inpaint" else {})
                    )
                model = load_sd_unet_checkpoint(sd, xcfg, lora, lora_strength)
                vae_cfg = sdxl_vae_config()
            elif family == "zimage-turbo":
                if lora:
                    raise ValueError("LoRA baking is not wired for zimage-turbo")
                # Looked up at call time: tests shrink models by patching the
                # package-level preset.
                from . import models as _models

                model = load_zimage_checkpoint(
                    sd, _models.zimage_turbo_config(), name=family
                )
                vae_cfg = flux_vae_config()  # the FLUX 16-channel autoencoder
            else:
                cfg = {
                    "flux-dev": flux_dev_config,
                    "flux-schnell": flux_schnell_config,
                }[family]()
                model = load_flux_checkpoint(
                    sd, cfg, lora, lora_strength, name=family
                )
                vae_cfg = flux_vae_config()
            model = maybe_quant(model)
            if family == "flux-schnell":
                # The host's FluxSchnell sampling settings: a discrete flow
                # table at shift 1.0 (dev keeps the widget's 1.15).
                model.sampler_prefs = {"shift": 1.0}
            elif family == "zimage-turbo":
                # The host's Z-Image sampling settings: the flow table at
                # shift 3.0, what the template's ModelSamplingAuraFlow says.
                model.sampler_prefs = {"shift": 3.0}
        if not load_vae:
            return model, None
        vae_sd = load_safetensors(vae_path) if vae_path else sd
        from .models.convert_vae import strip_vae_prefix

        if not any(
            k.startswith("decoder.") for k in strip_vae_prefix(vae_sd)
        ):
            raise ValueError(
                f"no VAE weights in {'vae_path' if vae_path else 'the checkpoint'} — "
                "flux/bare-UNet checkpoints don't bundle one; set vae_path to the "
                "autoencoder file (e.g. ae.safetensors)"
            )
        vae = load_vae_checkpoint(vae_sd, cfg=vae_cfg)
        return model, vae


class TPUCLIPLoader:
    """Tokenizer+encoder files → CLIP wire value (encoder plus its tokenizer)."""

    DESCRIPTION = "Load a CLIP/T5 text encoder and its tokenizer tables."
    RETURN_TYPES = ("CLIP",)
    RETURN_NAMES = ("clip",)
    FUNCTION = "load"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "encoder_path": ("STRING", {"default": ""}),
                "encoder_type": (
                    ["clip-l", "open-clip-g", "open-clip-h", "t5", "umt5", "qwen3",
                     "qwen25vl"],
                    {"default": "clip-l"},
                ),
            },
            "optional": {
                "vocab_path": ("STRING", {"default": "", "tooltip": "CLIP vocab.json"}),
                "merges_path": ("STRING", {"default": "", "tooltip": "CLIP merges.txt"}),
                "tokenizer_json": ("STRING", {"default": "", "tooltip": "tokenizer.json"}),
                "max_len": ("INT", {"default": 77, "min": 8, "max": 4096}),
            },
        }

    def load(
        self,
        encoder_path: str,
        encoder_type: str,
        vocab_path: str = "",
        merges_path: str = "",
        tokenizer_json: str = "",
        max_len: int = 77,
    ):
        from .models import load_clip_text_checkpoint, load_t5_checkpoint
        from .utils.tokenizer import CLIPBPETokenizer, load_tokenizer_json

        if encoder_type in ("qwen3", "qwen25vl"):
            # The decoder-only towers: a byte-level BPE table behind a chat
            # template, no fixed window (``max_len`` is CLIP's 77 unless
            # set; Qwen3's own budget is 512 tokens, Qwen-Image's pipeline gives
            # Qwen2.5-VL 1024 + the 34 of its system prompt, whose states the
            # encode node cuts off).
            from .models import load_qwen3_checkpoint, load_qwen25vl_checkpoint
            from .utils.tokenizer import (
                QWEN_CHAT_TEMPLATE,
                QWEN_IMAGE_CHAT_TEMPLATE,
                load_chat_tokenizer_json,
            )

            if not tokenizer_json:
                raise ValueError(
                    f"encoder_type={encoder_type!r} requires tokenizer_json "
                    "(Qwen's byte-level BPE tokenizer.json)"
                )
            vl = encoder_type == "qwen25vl"
            enc = (load_qwen25vl_checkpoint if vl else load_qwen3_checkpoint)(
                encoder_path)
            tok = load_chat_tokenizer_json(
                tokenizer_json,
                max_len=(1058 if vl else 512) if max_len == 77 else max_len,
                template=QWEN_IMAGE_CHAT_TEMPLATE if vl else QWEN_CHAT_TEMPLATE)
        elif encoder_type in ("t5", "umt5"):
            if not tokenizer_json:
                raise ValueError(
                    f"encoder_type={encoder_type!r} requires tokenizer_json (no "
                    "vocab.json/merges.txt form exists for these tokenizers)"
                )
            if encoder_type == "umt5":
                from .models import umt5_xxl_config

                enc = load_t5_checkpoint(encoder_path, umt5_xxl_config())
            else:
                enc = load_t5_checkpoint(encoder_path)
            tok = load_tokenizer_json(tokenizer_json, max_len=max_len, eos_id=1)
        else:
            cfg = None
            if encoder_type == "open-clip-h":
                from .models import open_clip_h_config

                cfg = open_clip_h_config()
            enc = load_clip_text_checkpoint(
                encoder_path, cfg=cfg,
                open_clip=encoder_type in ("open-clip-g", "open-clip-h")
            )
            if tokenizer_json:
                tok = load_tokenizer_json(tokenizer_json, max_len=max_len)
            elif vocab_path and merges_path:
                tok = CLIPBPETokenizer.from_files(
                    vocab_path, merges_path, max_len=max_len,
                    pad_id=(
                        0
                        if encoder_type in ("open-clip-g", "open-clip-h")
                        else None
                    ),
                )
            else:
                raise ValueError(
                    "CLIP loading needs tokenizer_json OR both vocab_path and "
                    "merges_path"
                )
        # Content stamp for the cross-request embed cache: a stable model
        # key (file identity — path + size + mtime, so an in-place file
        # replacement changes the key — plus tower config) so two loads of
        # one checkpoint share cache entries across prompts and restarts
        # of the wire.
        import hashlib as _hashlib

        from .models.embed_cache import file_stamp

        model_key = _hashlib.md5(repr(
            [file_stamp(encoder_path), encoder_type, max_len,
             vocab_path, merges_path, tokenizer_json],
        ).encode()).hexdigest()
        return ({"encoder": enc, "tokenizer": tok, "type": encoder_type,
                 "model_key": model_key},)


class TPUTextEncode:
    """(CLIP, text) → CONDITIONING: {'context', 'pooled'} wire dict."""

    DESCRIPTION = "Encode a prompt with a loaded text encoder."
    RETURN_TYPES = ("CONDITIONING",)
    RETURN_NAMES = ("conditioning",)
    FUNCTION = "encode"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "clip": ("CLIP", {}),
                "text": ("STRING", {"default": "", "multiline": True}),
            },
            "optional": {
                "clip_skip": (
                    "INT",
                    {"default": 0, "min": 0, "max": 2,
                     "tooltip": "host CLIPSetLastLayer semantics: 0 = model "
                                "default (SD2 towers auto-use penultimate), "
                                "1 = final layer, 2 = penultimate"},
                ),
            },
        }

    def encode(self, clip, text: str, clip_skip: int = 0):
        import jax.numpy as jnp

        if clip_skip == 0:
            # CLIPSetLastLayer shim tags the wire (nodes_compat.py); an
            # explicit widget value wins over the tag.
            clip_skip = int(clip.get("clip_skip", 0))
        if clip_skip in (-1, -2):
            # Host CLIPSetLastLayer convention (stop_at_clip_layer).
            clip_skip = -clip_skip
        if clip_skip not in (0, 1, 2):
            raise ValueError(
                f"clip_skip must be 0 (model default), 1/-1 (final layer) or "
                f"2/-2 (penultimate); got {clip_skip}"
            )
        ctype = clip.get("type")
        if ctype == "sdxl-dual":
            # Bundled SDXL towers (CheckpointLoaderSimple shim): encode both,
            # assemble the (2048-d context, 2816-d pooled) pair exactly like
            # TPUConditioningCombine(mode='sdxl') with stock 1024² size tags.
            from .models.text_encoders import sdxl_text_conditioning

            (cl,) = self.encode(clip["l"], text, clip_skip)
            (cg,) = self.encode(clip["g"], text, clip_skip)
            # Default (0) = penultimate, SDXL's training-time convention; an
            # explicit clip_skip selects per-tower streams via each tower's
            # own skip-resolved "context" (1 = final layer, 2 = penultimate).
            str_l = cl["penultimate"] if clip_skip == 0 else cl["context"]
            str_g = cg["penultimate"] if clip_skip == 0 else cg["context"]
            context, y = sdxl_text_conditioning(
                str_l, str_g, cg["pooled"], width=1024, height=1024,
            )
            return ({"context": context, "penultimate": None, "pooled": y},)
        if ctype == "sd3-triple":
            # Stock TripleCLIPLoader (or DualCLIPLoader type=sd3, any one
            # tower absent): encode every present tower and assemble SD3's
            # (context, y) — TPUConditioningCombine(mode='sd3') semantics in
            # one encode. Penultimate streams unconditionally: SD3 trains on
            # layer -2. A missing CLIP tower zero-fills, the stock SD3
            # CLIP's convention, and ALIGNMENT matters: the model was
            # trained with L at joint[0:768] and G at joint[768:2048], so a
            # missing L must still occupy its slot as zeros (canonical 768,
            # clamped so resized test towers compose — the same derived-
            # geometry rule as context_dim below) or G's features shift to
            # offset 0. A missing G needs only a width-0 stream: its slot is
            # trailing, and zeros ⊕ pad-to-4096 equals pad-to-4096. Pooled
            # halves zero-fill at the canonical widths (768/1280) so y keeps
            # the model's vec_in geometry.
            from .models.text_encoders import sd3_text_conditioning

            cl = cg = None
            if clip.get("l") is not None:
                (cl,) = self.encode(clip["l"], text, clip_skip)
            if clip.get("g") is not None:
                (cg,) = self.encode(clip["g"], text, clip_skip)
            if cl is None and cg is None:
                raise ValueError(
                    "sd3 conditioning needs at least one CLIP tower "
                    "(clip_l or clip_g); got T5 only"
                )
            t5_ctx = None
            if clip.get("t5") is not None:
                (ct5,) = self.encode(clip["t5"], text, clip_skip)
                t5_ctx = ct5["context"]
            # The sequence-concat requires the CLIP joint padded to the T5
            # width — 4096 for the real t5xxl, derived so resized towers
            # compose.
            context_dim = t5_ctx.shape[-1] if t5_ctx is not None else 4096
            present = cl if cl is not None else cg
            batch, seq = present["penultimate"].shape[:2]
            if cl is not None:
                l_pen, l_pooled = cl["penultimate"], cl["pooled"]
            else:
                g_width = cg["penultimate"].shape[-1]
                l_pen = jnp.zeros(
                    (batch, seq,
                     min(768, max(0, context_dim - g_width))),
                    jnp.float32,
                )
                l_pooled = jnp.zeros((batch, 768), jnp.float32)
            if cg is not None:
                g_pen, g_pooled = cg["penultimate"], cg["pooled"]
            else:
                g_pen = jnp.zeros((batch, seq, 0), jnp.float32)
                g_pooled = jnp.zeros((batch, 1280), jnp.float32)
            context, y = sd3_text_conditioning(
                l_pen, g_pen, l_pooled, g_pooled, t5_ctx,
                context_dim=context_dim,
            )
            return ({"context": context, "penultimate": None, "pooled": y},)
        if ctype == "flux-dual":
            # Stock DualCLIPLoader(type=flux): T5 context + CLIP-L pooled —
            # TPUConditioningCombine(mode='flux') semantics in one encode.
            (ct5,) = self.encode(clip["t5"], text, clip_skip)
            (cl,) = self.encode(clip["l"], text, clip_skip)
            return (
                {"context": ct5["context"], "penultimate": None,
                 "pooled": cl["pooled"]},
            )
        enc, tok = clip["encoder"], clip["tokenizer"]
        if enc is None or tok is None:
            raise ValueError(
                clip.get("tokenizer_error")
                or "CLIP wire has no encoder/tokenizer"
            )
        # Cross-request reuse (models/embed_cache.py): encoder outputs are
        # content-addressed on (model key, tower, token ids) — a hit skips
        # the encoder program entirely and returns the SAME arrays, so
        # cached-vs-fresh is bitwise-equal and same-prompt requests share
        # one cond object (the serving tier's sibling-seed broadcast seam).
        from .models import embed_cache
        from .utils.metrics import registry

        tower = clip["type"]
        ran = []

        def cached(mask, compute):
            return embed_cache.cached_encode(
                enc, clip.get("model_key"), tower, ids, mask,
                lambda: (ran.append(1), compute())[1],
            )

        # One span a tower a call: the host's tokenizer walk and the lookup,
        # and on a miss the dispatch of the tower's program (its device time
        # is the trace's, not the span's).
        with tracing.span("text-encode", cat="graph", tower=tower) as sp:
            ids, mask = tok([text])
            n_tokens = len(ids[0])
            dropped = None
            if tower in ("qwen3", "qwen25vl"):
                # Causal: the states of the valid tokens do not depend on
                # the padding after them, so the tower runs at the bucket's
                # length (one program a bucket) without a mask; the mask is
                # part of the cache key only.
                out = cached(mask, lambda: enc(jnp.asarray(ids, jnp.int32)))
                n_tokens = int(mask[0].sum())  # the VALID count, not the bucket
                if tower == "qwen25vl":
                    # Qwen-Image's wire: the states from the first token of
                    # the user's text to the last valid one — the system
                    # prompt's are cut off (by position: the tokenizer's
                    # ``prefix_length``) and no padding goes on.
                    dropped = tok.prefix_length(ids[0])
                    out = out[:, dropped:n_tokens]
                    n_tokens -= dropped
            elif tower in ("t5", "umt5"):
                # ``attention_mask`` False (the flux-dual wire): the source
                # hands the tower no mask, so padded keys take part.
                masked = clip.get("attention_mask", True)
                out = cached(
                    mask if masked else None,
                    lambda: enc(jnp.asarray(ids, jnp.int32),
                                mask=jnp.asarray(mask) if masked else None),
                )
                if masked:
                    n_tokens = int(mask[0].sum())  # the VALID count
            else:
                out = cached(None, lambda: enc(jnp.asarray(ids, jnp.int32)))
            cache = "miss" if ran else "hit"
            sp.set(tokens=n_tokens, cache=cache)
            if dropped is not None:
                sp.set(dropped=dropped)
        registry.counter(
            "pa_text_encode_total", labels={"tower": tower, "cache": cache},
            help="text-tower encodes by tower and embed-cache outcome",
        )
        if tower == "qwen3":
            # The single-stream denoiser has no pooled vector: the slot
            # carries the count of valid tokens a row, by which it replaces
            # the rest of the bucket with its learned pad token.
            return ({"context": out, "penultimate": None,
                     "pooled": jnp.asarray(mask.sum(-1, keepdims=True), jnp.float32)},)
        if tower == "qwen25vl":
            return ({"context": out, "pooled": None},)
        if tower == "umt5":
            # The WAN wire: the states of the valid tokens, zeros after them
            # (the published pipeline cuts each prompt's states at its length
            # and the model pads them back with zeros to its text length).
            out = out * jnp.asarray(mask, out.dtype)[..., None]
        if tower in ("t5", "umt5"):
            return ({"context": out, "pooled": None},)
        last, penultimate, pooled = out
        if clip_skip == 1:
            context = last
        elif clip_skip == 2:
            context = penultimate
        else:
            # Model default: SD2 towers (penultimate_ln configs) were trained
            # with penultimate-layer conditioning — route it automatically.
            context = (
                penultimate
                if getattr(enc.cfg, "penultimate_ln", False)
                else last
            )
        return (
            {
                "context": context,
                "penultimate": penultimate,
                "pooled": pooled,
            },
        )


class TPUConditioningCombine:
    """Assemble multi-tower conditioning:

    - ``sdxl``: CLIP-L + OpenCLIP-G CONDITIONINGs → 2048-d context ‖ 2816-d
      pooled/size vector (``sdxl_text_conditioning`` — what the SDXL UNet's
      cross-attention and label embed expect).
    - ``flux``: T5 CONDITIONING (context) + CLIP-L CONDITIONING (pooled vec) →
      the (context, y) pair the MMDiT consumes.
    - ``sd3``: CLIP-L (a) + OpenCLIP-G (b) [+ T5 (conditioning_c)] → the L⊕G
      joint stream padded to 4096 into the T5 context ‖ 2048-d pooled
      (``sd3_text_conditioning``).

    Without this node the individual towers' outputs are dimensionally wrong for
    those families — TPUTextEncode alone only serves SD1.5/SD2.x."""

    DESCRIPTION = "Combine text-encoder outputs for SDXL (L+G), FLUX (T5+CLIP), or SD3 (L+G+T5)."
    RETURN_TYPES = ("CONDITIONING",)
    RETURN_NAMES = ("conditioning",)
    FUNCTION = "combine"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "conditioning_a": (
                    "CONDITIONING",
                    {"tooltip": "CLIP-L (sdxl) / T5 (flux)"},
                ),
                "conditioning_b": (
                    "CONDITIONING",
                    {"tooltip": "OpenCLIP-G (sdxl) / CLIP-L (flux)"},
                ),
                "mode": (["sdxl", "flux", "sd3"], {"default": "sdxl"}),
            },
            "optional": {
                "width": ("INT", {"default": 1024, "min": 16, "max": 8192}),
                "height": ("INT", {"default": 1024, "min": 16, "max": 8192}),
                "conditioning_c": (
                    "CONDITIONING",
                    {"tooltip": "T5 (sd3; optional but recommended)"},
                ),
            },
        }

    def combine(
        self, conditioning_a, conditioning_b, mode: str,
        width: int = 1024, height: int = 1024, conditioning_c=None,
    ):
        if mode == "sd3":
            from .models.text_encoders import sd3_text_conditioning

            pen_l = conditioning_a.get("penultimate")
            pooled_l = conditioning_a.get("pooled")
            pen_g = conditioning_b.get("penultimate")
            pooled_g = conditioning_b.get("pooled")
            if pen_l is None or pen_g is None or pooled_l is None or pooled_g is None:
                raise ValueError(
                    "sd3 mode needs CLIP-L as a and OpenCLIP-G as b, both "
                    "from TPUTextEncode (penultimate + pooled)"
                )
            t5_ctx = conditioning_c["context"] if conditioning_c else None
            context, y = sd3_text_conditioning(
                pen_l, pen_g, pooled_l, pooled_g, t5_ctx
            )
            return ({"context": context, "pooled": y},)
        if mode == "flux":
            if conditioning_b.get("pooled") is None:
                raise ValueError("flux mode needs a CLIP conditioning (pooled) as b")
            return (
                {"context": conditioning_a["context"],
                 "pooled": conditioning_b["pooled"]},
            )
        from .models.text_encoders import sdxl_text_conditioning

        pen_l = conditioning_a.get("penultimate")
        pen_g = conditioning_b.get("penultimate")
        pooled_g = conditioning_b.get("pooled")
        if pen_l is None or pen_g is None or pooled_g is None:
            raise ValueError(
                "sdxl mode needs CLIP-L as a and OpenCLIP-G (with text_projection) "
                "as b, both from TPUTextEncode"
            )
        context, y = sdxl_text_conditioning(
            pen_l, pen_g, pooled_g, width=width, height=height
        )
        return ({"context": context, "pooled": y},)


class TPUEmptyLatent:
    """(width, height, batch) → LATENT noise-free zeros, ComfyUI-style."""

    DESCRIPTION = "Allocate an empty latent batch for sampling."
    RETURN_TYPES = ("LATENT",)
    RETURN_NAMES = ("latent",)
    FUNCTION = "generate"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "width": ("INT", {"default": 512, "min": 16, "max": 8192, "step": 8}),
                "height": ("INT", {"default": 512, "min": 16, "max": 8192, "step": 8}),
                "batch_size": ("INT", {"default": 1, "min": 1, "max": 64}),
                "channels": ("INT", {"default": 4, "min": 1, "max": 64}),
            }
        }

    def generate(self, width: int, height: int, batch_size: int, channels: int = 4):
        import jax.numpy as jnp

        return (
            {"samples": jnp.zeros((batch_size, height // 8, width // 8, channels))},
        )


class TPUVAEEncode:
    """(VAE, IMAGE) → LATENT — the img2img entry: encode pixels (floats in
    [0, 1], as TPUVAEDecode emits) to the latent an init-capable KSampler run
    starts from (denoise < 1)."""

    DESCRIPTION = "Encode images to latents for img2img / inpaint workflows."
    RETURN_TYPES = ("LATENT",)
    RETURN_NAMES = ("latent",)
    FUNCTION = "encode"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {"vae": ("VAE", {}), "image": ("IMAGE", {})},
            "optional": {
                "seed": ("INT", {"default": -1, "min": -1, "max": 2**31 - 1,
                                 "tooltip": "-1 = deterministic posterior mean; "
                                            ">=0 samples the posterior"}),
                "tile_size": ("INT", {"default": 0, "min": 0, "max": 4096,
                                      "step": 32,
                                      "tooltip": "0 = no tiling (pixels, "
                                                 "multiple of the VAE factor; "
                                                 "bounds encoder memory)"}),
            },
        }

    def encode(self, vae, image, seed: int = -1, tile_size: int = 0):
        import jax

        from .models.vae import encode_maybe_tiled, images_to_vae_input

        x = images_to_vae_input(image)
        if tile_size:
            if seed >= 0:
                raise ValueError(
                    "tiled encode is deterministic (posterior mean) — "
                    "seeded sampling and tile_size are exclusive"
                )
            return ({"samples": encode_maybe_tiled(vae, x, tile_size)},)
        rng = seed_key(seed) if seed >= 0 else None
        return ({"samples": vae.encode(x, rng)},)


# Resize methods shared by the two hi-res-fix siblings (latent- and
# image-space); both validate against it so a workflow typo gets a clear
# error instead of a jax internal one.
RESIZE_METHODS = ("nearest", "bilinear", "lanczos3")


class TPULatentUpscale:
    """(LATENT, scale) → LATENT resized in latent space — the hi-res-fix step
    between a low-res sample and a denoise<1 KSampler pass."""

    DESCRIPTION = "Resize latents (hi-res fix); follow with a denoise<1 KSampler."
    RETURN_TYPES = ("LATENT",)
    RETURN_NAMES = ("latent",)
    FUNCTION = "upscale"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "latent": ("LATENT", {}),
                "scale": ("FLOAT", {"default": 2.0, "min": 0.25, "max": 8.0,
                                    "step": 0.25}),
                "method": (list(RESIZE_METHODS), {"default": "bilinear"}),
            }
        }

    def upscale(self, latent, scale: float, method: str = "bilinear",
                scale_w: float | None = None):
        """``scale_w`` (optional, defaults to ``scale``) resizes width by its
        own factor — aspect-changing upscales, e.g. the stock LatentUpscale
        node's absolute width/height targets (nodes_compat.py)."""
        import jax

        if method not in RESIZE_METHODS:
            raise ValueError(
                f"method must be one of {RESIZE_METHODS}, got {method!r}"
            )

        z = latent["samples"]
        # Spatial dims are the two before channels (works for image 4-D and
        # video 5-D latents; time is never resized). Snap to even dims — odd
        # latent sizes break UNet stride-2 skip concats and DiT patchify, the
        # same boundary validation TPUKSampler applies.
        h, w = z.shape[-3], z.shape[-2]

        def snap(v: float) -> int:
            s = round(v)
            return s + (s % 2)

        th = snap(h * scale)
        tw = snap(w * (scale if scale_w is None else scale_w))
        if th < 2 or tw < 2:
            raise ValueError(
                f"scale {scale} shrinks the {h}x{w} latent to {th}x{tw}"
            )
        target = (*z.shape[:-3], th, tw, z.shape[-1])
        out = {**latent, "samples": jax.image.resize(z, target, method=method)}
        # A stale noise_mask no longer matches the spatial dims; rescale it too.
        if "noise_mask" in latent:
            m = latent["noise_mask"]
            out["noise_mask"] = jax.image.resize(
                m, (*m.shape[:-3], target[-3], target[-2], 1), method="bilinear"
            )
        return (out,)


class TPUSetLatentNoiseMask:
    """(LATENT, MASK) → LATENT with a noise mask attached — inpainting: the
    KSampler denoises only where mask=1 and re-pins mask=0 regions to the input
    latent at every step (ComfyUI SetLatentNoiseMask semantics)."""

    DESCRIPTION = "Attach an inpainting mask to a latent (1 = regenerate)."
    RETURN_TYPES = ("LATENT",)
    RETURN_NAMES = ("latent",)
    FUNCTION = "set_mask"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"latent": ("LATENT", {}), "mask": ("MASK", {})}}

    def set_mask(self, latent, mask):
        import jax
        import jax.numpy as jnp

        samples = latent["samples"]
        m = jnp.asarray(mask, jnp.float32)
        video = samples.ndim == 5
        if video and m.ndim == 3:
            # (B, H, W) spatial mask on a video latent: applies to every frame.
            m = m[:, None]
        if m.ndim == samples.ndim - 1:
            m = m[..., None]
        if m.ndim != samples.ndim:
            raise ValueError(
                f"mask rank {jnp.asarray(mask).ndim} does not fit latent rank "
                f"{samples.ndim} (expected a (B, H, W)"
                f"{' or (B, T, H, W)' if video else ''} mask)"
            )
        spatial = samples.shape[1:-1]
        if m.shape[1:-1] != spatial:
            target = (m.shape[0], *spatial, 1)
            if video and m.shape[1] == 1:
                # Broadcast frame axis: resize spatially only, keep T=1.
                target = (m.shape[0], 1, *spatial[1:], 1)
            m = jax.image.resize(m, target, method="bilinear")
        return ({**latent, "noise_mask": m},)


class TPUEmptyVideoLatent:
    """(width, height, frames, batch) → 5-D video LATENT zeros for the WAN
    family; frame count follows the causal 4k+1 schedule (81 by convention)."""

    DESCRIPTION = "Allocate an empty video latent batch (WAN-class, 5-D)."
    RETURN_TYPES = ("LATENT",)
    RETURN_NAMES = ("latent",)
    FUNCTION = "generate"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "width": ("INT", {"default": 832, "min": 16, "max": 8192, "step": 16}),
                "height": ("INT", {"default": 480, "min": 16, "max": 8192, "step": 16}),
                "frames": ("INT", {"default": 81, "min": 1, "max": 1024, "step": 4,
                                   "tooltip": "pixel frames; must be 1 mod 4 "
                                              "(causal VAE schedule)"}),
                "batch_size": ("INT", {"default": 1, "min": 1, "max": 16}),
                "channels": ("INT", {"default": 16, "min": 1, "max": 64}),
            }
        }

    def generate(
        self, width: int, height: int, frames: int, batch_size: int,
        channels: int | None = None,
    ):
        import jax.numpy as jnp

        from .models.video_vae import wan_vae_config

        cfg = wan_vae_config()
        t_lat = cfg.latent_frames(frames)  # raises on off-schedule counts
        f = cfg.spatial_factor
        if channels is None:
            # Default from the SAME config that owns the schedule/factors
            # (16 for real WAN) — every caller stays consistent with it.
            channels = cfg.z_channels
        return (
            {
                "samples": jnp.zeros(
                    (batch_size, t_lat, height // f, width // f, channels)
                )
            },
        )


def _scheduler_menu() -> list[str]:
    """The KSampler scheduler dropdown — sourced from the sampling layer's
    registry so the menu and make_sigmas dispatch cannot drift."""
    from .sampling import SCHEDULER_NAMES

    return list(SCHEDULER_NAMES)


_SHIFT_WIDGET_DEFAULT = 1.15


def _shift_from_prefs(model, shift: float) -> float:
    """Resolve the flow-shift the sampler actually runs with.

    ModelSamplingSD3/ModelSamplingFlux (stock schedule patches) attach a
    shift default to the MODEL via sampler_prefs; a shift widget left at its
    default (1.15) yields to it, an explicit non-default value wins — the
    same precedence RescaleCFG's cfg_rescale uses."""
    prefs = getattr(model, "sampler_prefs", None) or {}
    if shift == _SHIFT_WIDGET_DEFAULT and "shift" in prefs:
        return float(prefs["shift"])
    return shift


def _collect_control(positive) -> tuple:
    """Every control spec reachable from the positive conditioning: the
    top-level ``control`` tuple plus tags riding combined ``extras`` entries
    (ConditioningCombine moves the second cond — control tag included — into
    extras; dropping those silently would make control order-dependent)."""
    def tags(cond):
        c = cond.get("control") or ()
        return tuple(c) if isinstance(c, (list, tuple)) else (c,)

    specs = tags(positive)
    for e in positive.get("extras", ()):
        specs += tags(e)
    return specs


def _split_lora_delegate(model, positive):
    """(model, lora_factors) for the sampler call: a baked-LoRA model whose
    LoraLoader attached a clean serving delegate samples through the
    UNPATCHED base + per-request factors, so the continuous-batching
    scheduler seats it as a LoRA lane of the base model's bucket (any LoRA
    mix co-batches with plain traffic in one program; run_sampler merges the
    factors eagerly on inline legs). The bake stays authoritative whenever
    the request also carries state the factor recompose can't thread —
    multi-controlnet chains, inpaint, i2v."""
    delegate = getattr(model, "lora_delegate", None)
    if (delegate is None or not delegate.get("factors")
            or positive.get("inpaint") is not None
            or positive.get("i2v") is not None
            or len(_collect_control(positive)) > 1):
        return model, None
    return delegate["base"], delegate["factors"]


def _model_with_control(model, specs, inpaint=None, i2v=None):
    """Compose ControlNet residual injection into the MODEL (the ``control``
    tags Apply nodes leave on the positive conditioning — chained Apply nodes
    stack and their residuals sum, the host's multi-controlnet accumulation).
    The composition is a single merged DiffusionModel — every control trunk +
    the base trunk in one jit program — and a parallelized MODEL
    re-parallelizes the composition over its own chain/config, so DP/FSDP
    placement covers all the networks. Control therefore conditions every
    model call (cond AND uncond) — the host's ControlNetApplyAdvanced
    semantics; for the plain positive-only ControlNetApply this is a
    documented divergence (stock scopes it to cond).

    The composition is CACHED on the base model keyed by the spec identities
    (strong refs held, so ids stay valid) and stays resident across prompts —
    re-running with the same ControlNet setup reuses the placed params and
    compiled programs instead of paying placement + XLA compile per prompt.
    A different setup replaces the cache entry (the old composition's
    placement is cleaned up); memory note: for a parallelized MODEL the base
    placement (the cached workflow output) and the composed placement coexist
    while control is in use — a placement OOM degrades through the normal
    drop-device path."""
    if not specs and not inpaint and not i2v:
        return model
    from .models.api import DiffusionModel
    from .models.controlnet import apply_control
    from .models.unet import apply_inpaint_conditioning
    from .models.wan import apply_i2v_conditioning
    from .parallel.orchestrator import ParallelModel, parallelize

    key = tuple(
        (id(s["model"]), id(s["hint"]), float(s.get("strength", 1.0)),
         float(s.get("start_percent", 0.0)), float(s.get("end_percent", 1.0)))
        for s in specs
    ) + ((id(inpaint["mask"]), id(inpaint["masked_latent"]))
         if inpaint else ()) + (
        (id(i2v.get("cond")), id(i2v.get("clip_fea"))) if i2v else ()
    )
    cached = getattr(model, "_control_composed", None)
    if cached is not None and cached[0] == key:
        return cached[1]

    def compose(base):
        if i2v:
            # Innermost: the WAN i2v channel-concat (+ optional CLIP branch)
            # wraps the raw model; control residuals apply to the wrapped step.
            base = apply_i2v_conditioning(
                base, i2v.get("cond"), i2v.get("clip_fea")
            )
        if inpaint:
            # Innermost: the 9-channel input convention wraps the raw model;
            # control residuals then apply to the wrapped step.
            base = apply_inpaint_conditioning(
                base, inpaint["mask"], inpaint["masked_latent"]
            )
        for spec in specs:
            base = apply_control(
                base, spec["model"], spec["hint"],
                strength=float(spec.get("strength", 1.0)),
                start_percent=float(spec.get("start_percent", 0.0)),
                end_percent=float(spec.get("end_percent", 1.0)),
            )
        return base

    if isinstance(model, ParallelModel):
        if model._pipeline_spec is not None:
            from .utils.logging import get_logger

            get_logger().info(
                "ControlNet composition: batch==1 pipeline placement is "
                "unavailable for the composed model (no staged decomposition "
                "of the control trunk) — DP/single-device routing only"
            )
        base = DiffusionModel(
            apply=model._apply, params=model._host_params,
            config=model.model_config,
        )
        composed = parallelize(compose(base), model.chain, config=model.config)
    else:
        if not (hasattr(model, "apply") and hasattr(model, "params")):
            raise ValueError(
                "ControlNet needs a MODEL with (apply, params) — wire the "
                "loader output (optionally through ParallelAnything) into "
                "the sampler"
            )
        composed = compose(model)
    if cached is not None and hasattr(cached[1], "cleanup"):
        cached[1].cleanup()  # a replaced composition frees its placement
    # specs/inpaint/i2v kept in the entry: the id()-based key stays valid only
    # while the tagged objects are alive.
    try:
        object.__setattr__(
            model, "_control_composed", (key, composed, specs, inpaint, i2v)
        )
    except (AttributeError, TypeError):
        pass  # uncacheable model object: composition still works, uncached
    return composed


def _prepare_sampling_inputs(model, positive, negative, latent, rng=None):
    """Shared sampler-node boundary (TPUKSampler + TPUSamplerCustomAdvanced):
    conditioning batch broadcast (ComfyUI semantics: one encoded prompt
    conditions the whole latent batch, tiled when it divides evenly),
    patch-size divisibility validation (a mismatch otherwise dies deep in
    patchify with an opaque reshape error), the missing-pooled FLUX warning,
    and uncond kwargs assembly.

    Returns ``(model_cfg, context, pooled, uncond_context, uncond_kwargs,
    cond_extra)`` where ``cond_extra`` is the multi-cond kwargs dict for
    ``run_sampler`` (``extra_conds`` / ``cond_area`` / ``cond_strength`` —
    the stock ConditioningCombine/SetArea wire)."""
    from .parallel.orchestrator import model_config_of
    from .sampling.k_samplers import broadcast_cond_batch

    shape = latent["samples"].shape
    batch = shape[0]

    def bcast(arr):
        return broadcast_cond_batch(arr, batch)

    context = bcast(positive["context"])
    pooled = bcast(positive.get("pooled"))
    model_cfg = model_config_of(model)
    patch = getattr(model_cfg, "patch_size", None)
    if isinstance(patch, int):
        bad = [d for d in shape[1:3] if d % patch]
        if bad:
            raise ValueError(
                f"latent spatial dims {shape[1:3]} must be multiples of the "
                f"model patch size {patch}"
            )
    if pooled is None and hasattr(model_cfg, "vec_in_dim"):
        from .utils.logging import get_logger

        get_logger().warning(
            "FLUX-family model sampled without a pooled vector (y falls back "
            "to zeros) — route T5 + CLIP conditioning through "
            "TPUConditioningCombine(mode='flux')"
        )
    uncond_context = bcast(negative["context"]) if negative else None
    uncond_kwargs = (
        {"y": bcast(negative["pooled"])}
        if negative and negative.get("pooled") is not None
        else None
    )
    adm = getattr(model_cfg, "adm_in_channels", None)
    if positive.get("unclip") and adm:
        # SD2.x-unCLIP: the adm vector comes from the unCLIPConditioning tags
        # (noise-augmented CLIP image embeds ‖ level embedding); an untagged
        # negative samples against zeros — host SD21UNCLIP.encode_adm.
        import jax.numpy as jnp

        from .models.unet import unclip_adm

        pooled = bcast(unclip_adm(positive["unclip"], adm, rng=rng))
        uncond_kwargs = {
            "y": (
                bcast(unclip_adm(negative["unclip"], adm, rng=rng))
                if negative and negative.get("unclip")
                else jnp.zeros_like(pooled)
            )
        }
    elif adm:
        # adm models sampled without an adm-shaped pooled: stock zero-fills
        # (SD21UNCLIP.encode_adm for untagged conditioning; SDXL encode_adm
        # defaults a missing pooled_output to zeros) rather than erroring.
        # On sd21-unclip the TEXT tower's 1024-wide pooled is dropped — it
        # never feeds the 1536/2048 label_emb; a wrong-width pooled on other
        # adm families (bare SDXL CLIPTextEncode wiring) raises with the fix.
        import jax.numpy as jnp

        def adm_or_none(vec, what):
            if vec is not None and vec.shape[-1] != adm:
                if getattr(model_cfg, "context_dim", None) == 1024:
                    return None
                raise ValueError(
                    f"{what} pooled vector is {vec.shape[-1]}-wide but this "
                    f"model's adm head expects {adm} — route the prompt "
                    "through CLIPTextEncodeSDXL / "
                    "TPUConditioningCombine(mode='sdxl')"
                )
            return vec

        pooled = adm_or_none(pooled, "positive")
        if pooled is None:
            pooled = jnp.zeros((batch, adm), jnp.float32)
        # The NEGATIVE side needs the same treatment: uncond_kwargs was
        # assigned from negative["pooled"] above, and a 1024-wide text pooled
        # there would reach label_emb on the uncond half of CFG.
        uncond_y = adm_or_none(
            uncond_kwargs.get("y") if uncond_kwargs else None, "negative"
        )
        if negative:
            uncond_kwargs = {
                "y": uncond_y if uncond_y is not None
                else jnp.zeros((batch, adm), jnp.float32)
            }
    # Multi-cond wire (stock ConditioningCombine/SetArea shims): extra conds
    # ride the positive dict's "extras" tuple; a SetArea on the primary rides
    # "area"/"strength". Negative-side extras have no uncond slot — warn and
    # sample with the primary negative only (documented divergence).
    extras = [
        {**e, "context": bcast(e["context"]),
         "pooled": bcast(e.get("pooled"))}
        for e in positive.get("extras", ())
    ]
    if negative and (negative.get("extras") or negative.get("area") is not None
                     or negative.get("area_pct") is not None
                     or negative.get("mask") is not None):
        from .utils.logging import get_logger

        get_logger().warning(
            "combined/area NEGATIVE conditioning is not supported — sampling "
            "with the primary negative prompt, full-frame"
        )
    if positive.get("timestep_range") is not None:
        from .utils.logging import get_logger

        get_logger().warning(
            "ConditioningSetTimestepRange on the PRIMARY positive cond is "
            "ignored (a step with no active conditioning has no fallback) — "
            "route ranged prompts through ConditioningCombine so they ride "
            "the extras, where the window gates them"
        )
    if negative and negative.get("timestep_range") is not None:
        from .utils.logging import get_logger

        get_logger().warning(
            "ConditioningSetTimestepRange on the NEGATIVE conditioning is "
            "not supported — the negative prompt applies across the whole run"
        )
    if negative and negative.get("control"):
        from .utils.logging import get_logger

        get_logger().warning(
            "a ControlNet tag on the NEGATIVE conditioning is ignored — "
            "control composes into the MODEL from the positive tag and "
            "conditions cond AND uncond calls alike (ControlNetApplyAdvanced "
            "semantics)"
        )
    cond_extra = {
        "extra_conds": extras,
        "cond_area": positive.get("area"),
        "cond_area_pct": positive.get("area_pct"),
        "cond_mask": positive.get("mask"),
        "cond_strength": float(positive.get("strength", 1.0)),
        "cond_mask_strength": float(positive.get("mask_strength", 1.0)),
    }
    return model_cfg, context, pooled, uncond_context, uncond_kwargs, cond_extra


class TPUKSampler:
    """(MODEL, positive, negative, LATENT) → LATENT — the per-step driver whose
    forwards route through the parallel scheduler when MODEL came from
    ParallelAnything (the reference's KSampler relationship, 1287)."""

    DESCRIPTION = "Sample latents with the loaded (optionally parallelized) model."
    RETURN_TYPES = ("LATENT",)
    RETURN_NAMES = ("latent",)
    FUNCTION = "sample"
    CATEGORY = CATEGORY


    @classmethod
    def INPUT_TYPES(cls):
        from .sampling.runner import SAMPLER_NAMES

        return {
            "required": {
                "model": ("MODEL", {}),
                "positive": ("CONDITIONING", {}),
                "latent": ("LATENT", {}),
                "seed": ("INT", {"default": 0, "min": 0, "max": SEED_MAX}),
                "steps": ("INT", {"default": 20, "min": 1, "max": 200}),
                "cfg": ("FLOAT", {"default": 7.5, "min": 1.0, "max": 30.0}),
                "sampler_name": (list(SAMPLER_NAMES), {"default": "dpmpp_2m"}),
            },
            "optional": {
                "negative": ("CONDITIONING", {}),
                "guidance": (
                    "FLOAT",
                    {"default": 3.5, "min": 0.0, "max": 30.0,
                     "tooltip": "flux-dev distilled guidance embed; 0 disables "
                                "(schnell)"},
                ),
                "shift": (
                    "FLOAT",
                    {"default": 1.15, "min": 0.25, "max": 8.0,
                     "tooltip": "rectified-flow timestep shift (flow_euler only)"},
                ),
                "denoise": (
                    "FLOAT",
                    {"default": 1.0, "min": 0.01, "max": 1.0, "step": 0.01,
                     "tooltip": "img2img strength: < 1 starts from the input "
                                "LATENT (wire a VAE Encode) instead of noise"},
                ),
                "scheduler": (
                    _scheduler_menu(),
                    {"default": "karras",
                     "tooltip": "sigma spacing for the k-samplers"},
                ),
                "cfg_rescale": (
                    "FLOAT",
                    {"default": 0.0, "min": 0.0, "max": 1.0, "step": 0.05,
                     "tooltip": "CFG rescale phi (Lin et al.): tames high-cfg "
                                "over-saturation, esp. v-prediction models"},
                ),
                "compile_loop": (
                    "BOOLEAN",
                    {"default": False,
                     "tooltip": "compile the WHOLE denoise loop into one XLA "
                                "program (zero per-step dispatch; single-"
                                "program chains only — hybrid chains fall "
                                "back to the eager loop)"},
                ),
            },
        }

    def sample(
        self,
        model,
        positive,
        latent,
        seed: int,
        steps: int,
        cfg: float,
        sampler_name: str,
        negative=None,
        guidance: float = 3.5,
        shift: float = 1.15,
        denoise: float = 1.0,
        scheduler: str = "karras",
        cfg_rescale: float = 0.0,
        compile_loop: bool = False,
    ):
        import jax
        import jax.numpy as jnp

        from .sampling.runner import run_sampler

        rng = seed_key(seed)
        shape = latent["samples"].shape
        noise = jax.random.normal(rng, shape, jnp.float32)
        shift = _shift_from_prefs(model, shift)
        model_cfg, context, pooled, uncond_context, uncond_kwargs, cond_extra = (
            _prepare_sampling_inputs(model, positive, negative, latent,
                                     rng=rng)
        )
        model, lora = _split_lora_delegate(model, positive)
        model = _model_with_control(
            model, _collect_control(positive), inpaint=positive.get("inpaint"),
            i2v=positive.get("i2v"),
        )
        kwargs = {} if pooled is None else {"y": pooled}
        out = run_sampler(
            model, noise, context, sampler=sampler_name, steps=steps,
            cfg_scale=cfg, uncond_context=uncond_context,
            uncond_kwargs=uncond_kwargs, rng=rng, shift=shift, **cond_extra,
            guidance=guidance if guidance > 0 else None,
            scheduler=scheduler,
            cfg_rescale=cfg_rescale,
            compile_loop=compile_loop,
            prediction=getattr(model_cfg, "prediction", "eps"),
            init_latent=(
                latent["samples"]
                if (denoise < 1.0 or "noise_mask" in latent)
                else None
            ),
            denoise=denoise,
            latent_mask=latent.get("noise_mask"),
            lora=lora,
            **kwargs,
        )
        return ({"samples": out},)


class TPUKSamplerAdvanced:
    """The host's KSamplerAdvanced: a KSampler whose denoise run covers an
    explicit step window [start_at_step, end_at_step) of the full ``steps``
    schedule — the stock SDXL base→refiner template's driver (base renders
    steps 0..N with leftover noise, the refiner continues N..end from the
    base's latent with ``add_noise`` disabled).

    Semantics matched to stock: ``add_noise="disable"`` drives the run with a
    zero noise tensor (the latent arrives already-noised from the previous
    stage); ``return_with_leftover_noise="enable"`` stops the ladder at
    sigma[end_at_step] without denoising to zero (the leftover the next stage
    consumes); with it disabled and ``end_at_step < steps`` the final sigma is
    forced to 0 (stock's force_full_denoise). Host-provided builtin the
    reference's workflows drive steps through
    (any_device_parallel.py:1287 assumes the host sampler calls forward)."""

    DESCRIPTION = "Sample a step window of the schedule (base→refiner driver)."
    RETURN_TYPES = ("LATENT",)
    RETURN_NAMES = ("latent",)
    FUNCTION = "sample"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        from .sampling.runner import SAMPLER_NAMES

        return {
            "required": {
                "model": ("MODEL", {}),
                "add_noise": (["enable", "disable"], {"default": "enable"}),
                "noise_seed": ("INT", {"default": 0, "min": 0, "max": SEED_MAX}),
                "steps": ("INT", {"default": 20, "min": 1, "max": 200}),
                "cfg": ("FLOAT", {"default": 8.0, "min": 1.0, "max": 30.0}),
                "sampler_name": (list(SAMPLER_NAMES), {"default": "euler"}),
                "scheduler": (_scheduler_menu(), {"default": "normal"}),
                "positive": ("CONDITIONING", {}),
                "negative": ("CONDITIONING", {}),
                "latent_image": ("LATENT", {}),
                "start_at_step": ("INT", {"default": 0, "min": 0, "max": 10000}),
                "end_at_step": ("INT", {"default": 10000, "min": 0,
                                        "max": 10000}),
                "return_with_leftover_noise": (["enable", "disable"],
                                               {"default": "disable"}),
            },
            "optional": {
                "shift": ("FLOAT", {"default": 1.15, "min": 0.25, "max": 8.0}),
                "compile_loop": ("BOOLEAN", {"default": False}),
            },
        }

    def sample(self, model, add_noise: str, noise_seed: int, steps: int,
               cfg: float, sampler_name: str, scheduler: str, positive,
               negative, latent_image, start_at_step: int, end_at_step: int,
               return_with_leftover_noise: str, shift: float = 1.15,
               compile_loop: bool = False):
        import jax
        import jax.numpy as jnp

        from .sampling.runner import run_sampler

        latent = latent_image
        shift = _shift_from_prefs(model, shift)
        (sigmas,) = TPUBasicScheduler().get_sigmas(
            model, scheduler, steps, denoise=1.0, shift=shift
        )
        realized = len(sigmas) - 1  # dedup schedulers may realize fewer
        start = min(start_at_step, realized)
        end = min(end_at_step, realized)
        if end <= start:
            return (dict(latent),)  # empty window: stock returns the latent
        sigmas = sigmas[start:end + 1]
        if return_with_leftover_noise != "enable" and end < realized:
            sigmas = sigmas.at[-1].set(0.0)  # stock force_full_denoise

        shape = latent["samples"].shape
        rng = seed_key(noise_seed)
        noise = (
            jax.random.normal(rng, shape, jnp.float32)
            if add_noise == "enable"
            else jnp.zeros(shape, jnp.float32)
        )
        model_cfg, context, pooled, uncond_context, uncond_kwargs, cond_extra = (
            _prepare_sampling_inputs(model, positive, negative, latent,
                                     rng=rng)
        )
        model, lora = _split_lora_delegate(model, positive)
        model = _model_with_control(
            model, _collect_control(positive), inpaint=positive.get("inpaint"),
            i2v=positive.get("i2v"),
        )
        kwargs = {} if pooled is None else {"y": pooled}
        out = run_sampler(
            model, noise, context, sampler=sampler_name,
            steps=max(1, len(sigmas) - 1), sigmas=sigmas,
            cfg_scale=cfg, uncond_context=uncond_context,
            uncond_kwargs=uncond_kwargs, rng=rng, shift=shift, **cond_extra,
            guidance=positive.get("guidance"),
            prediction=getattr(model_cfg, "prediction", "eps"),
            init_latent=latent["samples"],
            latent_mask=latent.get("noise_mask"),
            compile_loop=compile_loop,
            lora=lora,
            **kwargs,
        )
        last = float(sigmas[-1])
        if (return_with_leftover_noise == "enable" and 0.0 < last < 1.0
                and getattr(model_cfg, "prediction", "eps") == "flow"):
            # Stock hands a flow model's leftover-noise latent on divided by
            # (1 − σ_end) (``inverse_noise_scaling``); the next window's
            # noising, σ·0 + (1 − σ)·latent with its noise disabled, multiplies
            # it back: the second run continues from this run's state at σ_end.
            out = out / (1.0 - last)
        return ({"samples": out},)


class TPUVAEDecode:
    """(VAE, LATENT) → IMAGE floats in [0, 1]; tiled when the latent is large."""

    DESCRIPTION = "Decode latents to images (auto-tiled for large resolutions)."
    RETURN_TYPES = ("IMAGE",)
    RETURN_NAMES = ("image",)
    FUNCTION = "decode"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {"vae": ("VAE", {}), "latent": ("LATENT", {})},
            "optional": {
                "tile_size": ("INT", {"default": 0, "min": 0, "max": 512,
                                      "tooltip": "0 = no tiling"}),
            },
        }

    def decode(self, vae, latent, tile_size: int = 0):
        from .models.vae import decode_maybe_tiled, vae_output_to_images
        from .serving.decode import get_decode_queue

        # Batched tail decode (serving/decode.py): when the server installed
        # a decode queue, eligible latents batch into a shared compiled
        # decode dispatch instead of serializing inline behind the next
        # prompt's denoise. Ineligible work (tiled, video, odd rank) falls
        # through to the inline path unchanged.
        q = get_decode_queue()
        if q is not None:
            ticket = q.submit(vae, latent["samples"], tile_size)
            if ticket is not None:
                return (vae_output_to_images(ticket.result()),)
        return (vae_output_to_images(decode_maybe_tiled(vae, latent["samples"], tile_size)),)


def resolve_save_target(filename_prefix: str, output_dir: str = "",
                        suffix: str = "png") -> tuple:
    """Shared host-SaveImage path semantics for every save-family node:
    empty ``output_dir`` = the served PA_OUTPUT_DIR root; the prefix may carry
    a subfolder ("run1/img", created + counted within); absolute or
    parent-escaping prefixes are rejected; the numbered counter continues past
    the HIGHEST existing ``{name}_{N}.{suffix}`` index so re-runs never
    overwrite. Returns ``(target_dir, name, start_index)``."""
    import os
    import re as _re

    output_dir = output_dir or os.environ.get("PA_OUTPUT_DIR", "output")
    subdir, name = os.path.split(filename_prefix)
    target_dir = os.path.join(output_dir, subdir) if subdir else output_dir
    root = os.path.realpath(output_dir)
    if os.path.commonpath([root, os.path.realpath(target_dir)]) != root:
        raise ValueError(
            f"filename_prefix {filename_prefix!r} resolves outside "
            f"output_dir {output_dir!r}"
        )
    os.makedirs(target_dir, exist_ok=True)
    pat = _re.compile(_re.escape(name) + r"_(\d+)\." + _re.escape(suffix) + "$")
    taken = [
        int(m.group(1)) for f in os.listdir(target_dir) if (m := pat.match(f))
    ]
    return target_dir, name, (max(taken) + 1 if taken else 0)


class TPUSaveImage:
    """IMAGE → PNG files on disk — the terminal node every exported ComfyUI
    txt2img workflow ends with (the reference relies on the host's SaveImage;
    standalone, the framework supplies its own). Returns the written paths."""

    DESCRIPTION = "Save a batch of images as numbered PNGs."
    RETURN_TYPES = ("PATHS",)
    RETURN_NAMES = ("paths",)
    FUNCTION = "save"
    CATEGORY = CATEGORY
    OUTPUT_NODE = True

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "images": ("IMAGE", {}),
                "filename_prefix": ("STRING", {"default": "tpu"}),
            },
            "optional": {
                "output_dir": (
                    "STRING",
                    {"default": "",
                     "tooltip": "empty = $PA_OUTPUT_DIR, else ./output — the "
                                "same root the API server serves /view from"},
                ),
                "metadata": (
                    "STRING",
                    {"default": "", "multiline": True,
                     "tooltip": "embedded as the PNG 'parameters' text chunk "
                                "(the A1111-style key most galleries/readers "
                                "parse)"},
                ),
            },
            # Host-injected (ComfyUI executor semantics): the whole workflow
            # dict, embedded as the 'prompt' PNG chunk so a saved image can be
            # dragged back into a graph editor to restore its workflow.
            "hidden": {"prompt": "PROMPT"},
        }

    def save(self, images, filename_prefix: str = "tpu", output_dir: str = "",
             metadata: str = "", prompt=None):
        import os

        import jax.numpy as jnp

        from .utils import png_encode

        # Shared host-SaveImage path semantics (resolve_save_target):
        # PA_OUTPUT_DIR default, subfolder prefixes, escape rejection, and a
        # past-highest-index counter.
        target_dir, name, start = resolve_save_target(
            filename_prefix, output_dir, "png"
        )
        # The node's time in three parts: the wait for the device to finish
        # what the graph enqueued (the sync the fetch performs anyway, split
        # out); the quantise and row filter where the images are, and the copy
        # of those bytes (a quarter of the floats) to the host; the deflate on
        # the pool's threads and the file writes. The chip is idle under the
        # last two. A 3-D input is one image; a 5-D one is video floats
        # (B, F, H, W, 3) — the WAN decode shape: every frame of every clip is
        # written as its own numbered PNG, in order.
        images = jnp.asarray(images)
        with tracing.span("device-wait", cat="graph"):
            images.block_until_ready()
        with tracing.span("image-fetch", cat="graph") as sp:
            planes = png_encode.filter_rows(images)
            sp.set(bytes=planes.nbytes)
        chunks = []
        if metadata or prompt is not None:
            import json as _json

            from PIL.PngImagePlugin import PngInfo

            pnginfo = PngInfo()
            if metadata:
                pnginfo.add_text("parameters", metadata)
            if prompt is not None:
                try:
                    pnginfo.add_text("prompt", _json.dumps(prompt, default=repr))
                except Exception:
                    pass  # unserializable custom-node state: skip, still save
            chunks = pnginfo.chunks
        paths = tuple(os.path.join(target_dir, f"{name}_{start + i:05d}.png")
                      for i in range(len(planes)))
        png_encode.write_pngs(planes, images.shape[-1], paths, chunks)
        return (paths,)


class TPULoadImage:
    """Image file → (IMAGE floats in [0,1], MASK from alpha) — the img2img /
    inpaint entry node of exported workflows (host LoadImage semantics: mask is
    1 where the alpha channel is transparent; zeros when no alpha)."""

    DESCRIPTION = "Load an image file as IMAGE (+ alpha-derived MASK)."
    RETURN_TYPES = ("IMAGE", "MASK")
    RETURN_NAMES = ("image", "mask")
    FUNCTION = "load"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"image_path": ("STRING", {"default": ""})}}

    def load(self, image_path: str):
        import jax.numpy as jnp
        import numpy as np
        from PIL import Image, ImageOps

        img = Image.open(image_path)
        # Camera JPEGs carry orientation in EXIF; the host LoadImage applies it
        # before handing pixels downstream — match that.
        img = ImageOps.exif_transpose(img)
        # Convert FIRST: palette-mode PNGs carry transparency without an 'A'
        # band, and RGBA conversion materializes it into the alpha channel.
        rgba = np.asarray(img.convert("RGBA"), np.float32) / 255.0
        image = jnp.asarray(rgba[None, :, :, :3])
        alpha = rgba[None, :, :, 3]
        mask = (
            jnp.asarray(1.0 - alpha)
            if float(alpha.min()) < 1.0
            else jnp.zeros(image.shape[:3], jnp.float32)
        )
        return (image, mask)


class TPUImageScale:
    """IMAGE → resized IMAGE (bilinear/nearest/lanczos) — the image-space half
    of the hi-res-fix surface (TPULatentUpscale covers latent space)."""

    DESCRIPTION = "Resize images to an exact width/height."
    RETURN_TYPES = ("IMAGE",)
    RETURN_NAMES = ("image",)
    FUNCTION = "scale"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "image": ("IMAGE", {}),
                # step 8: diffusion consumers need factor-of-8-aligned pixel
                # dims (TPUEmptyLatent uses the same step; TPUKSampler's
                # boundary validation rejects misaligned latents).
                "width": ("INT", {"default": 1024, "min": 8, "max": 16384,
                                  "step": 8}),
                "height": ("INT", {"default": 1024, "min": 8, "max": 16384,
                                   "step": 8}),
                "method": (list(RESIZE_METHODS), {"default": "bilinear"}),
            }
        }

    def scale(self, image, width: int, height: int, method: str = "bilinear"):
        import jax
        import jax.numpy as jnp

        if method not in RESIZE_METHODS:
            raise ValueError(
                f"method must be one of {RESIZE_METHODS}, got {method!r}"
            )
        img = jnp.asarray(image)
        if img.ndim == 3:
            img = img[None]
        out = jax.image.resize(
            img, (img.shape[0], height, width, img.shape[-1]), method=method
        )
        return (jnp.clip(out, 0.0, 1.0),)


class TPURandomNoise:
    """seed → NOISE — the host's custom-sampling noise source (RandomNoise).
    The wire carries the seed; SamplerCustomAdvanced generates noise shaped
    like the latent it receives, exactly as the host's NOISE object does."""

    DESCRIPTION = "Noise source for the custom-sampling graph."
    RETURN_TYPES = ("NOISE",)
    RETURN_NAMES = ("noise",)
    FUNCTION = "get_noise"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "noise_seed": ("INT", {"default": 0, "min": 0, "max": SEED_MAX}),
        }}

    def get_noise(self, noise_seed: int):
        return ({"seed": int(noise_seed)},)


class TPUKSamplerSelect:
    """sampler_name → SAMPLER — the host's KSamplerSelect."""

    DESCRIPTION = "Pick the sampler for the custom-sampling graph."
    RETURN_TYPES = ("SAMPLER",)
    RETURN_NAMES = ("sampler",)
    FUNCTION = "get_sampler"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        from .sampling.runner import SAMPLER_NAMES

        return {"required": {
            "sampler_name": (list(SAMPLER_NAMES), {"default": "euler"}),
        }}

    def get_sampler(self, sampler_name: str):
        return ({"sampler": sampler_name},)


class TPUBasicScheduler:
    """(MODEL, scheduler, steps, denoise) → SIGMAS — the host's BasicScheduler:
    the named spacing over the MODEL's sigma space (flow models range over the
    shift-warped CONST table; eps/v over the alpha-bar table), with the host's
    denoise semantics (steps/denoise total, last steps+1 kept)."""

    DESCRIPTION = "Compute the sigma schedule for the custom-sampling graph."
    RETURN_TYPES = ("SIGMAS",)
    RETURN_NAMES = ("sigmas",)
    FUNCTION = "get_sigmas"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "model": ("MODEL", {}),
                "scheduler": (_scheduler_menu(), {"default": "normal"}),
                "steps": ("INT", {"default": 20, "min": 1, "max": 200}),
                "denoise": ("FLOAT", {"default": 1.0, "min": 0.01, "max": 1.0,
                                      "step": 0.01}),
            },
            "optional": {
                "shift": ("FLOAT", {
                    "default": 1.15, "min": 0.25, "max": 8.0,
                    "tooltip": "rectified-flow timestep shift (flow models; "
                               "the host sets this via ModelSamplingFlux)"}),
            },
        }

    def get_sigmas(self, model, scheduler: str, steps: int, denoise: float,
                   shift: float = 1.15):
        from .parallel.orchestrator import model_config_of
        from .sampling.k_samplers import flow_sigma_table, make_sigmas

        shift = _shift_from_prefs(model, shift)
        total = max(steps, int(round(steps / denoise))) if denoise < 1.0 else steps
        if getattr(model_config_of(model), "prediction", "eps") == "flow":
            sigmas = make_sigmas(scheduler, total,
                                 sigma_table=flow_sigma_table(shift))
        else:
            sigmas = make_sigmas(scheduler, total)
        if denoise < 1.0:
            # Same degenerate-schedule guard as run_sampler's truncation: a
            # scheduler that realizes fewer sigmas than requested (beta dedup)
            # would otherwise keep the WHOLE ladder and silently run at full
            # strength.
            realized = len(sigmas) - 1
            if realized > steps:
                sigmas = sigmas[-(steps + 1):]
            else:
                keep = min(realized, max(1, round(steps * realized / total)))
                sigmas = sigmas[-(keep + 1):]
        return (sigmas,)


class TPUFluxGuidance:
    """(CONDITIONING, guidance) → CONDITIONING — the host's FluxGuidance: tags
    the conditioning with the FLUX-dev distilled-guidance value the sampler
    feeds to the model's guidance embed."""

    DESCRIPTION = "Attach flux distilled guidance to a conditioning."
    RETURN_TYPES = ("CONDITIONING",)
    RETURN_NAMES = ("conditioning",)
    FUNCTION = "append"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "conditioning": ("CONDITIONING", {}),
            "guidance": ("FLOAT", {"default": 3.5, "min": 0.0, "max": 100.0}),
        }}

    def append(self, conditioning, guidance: float):
        return ({**conditioning, "guidance": float(guidance)},)


class TPUBasicGuider:
    """(MODEL, CONDITIONING) → GUIDER — the host's BasicGuider: unguided
    (cfg=1) sampling driver for distilled models (FLUX)."""

    DESCRIPTION = "Guider without CFG (distilled models)."
    RETURN_TYPES = ("GUIDER",)
    RETURN_NAMES = ("guider",)
    FUNCTION = "get_guider"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "model": ("MODEL", {}),
            "conditioning": ("CONDITIONING", {}),
        }}

    def get_guider(self, model, conditioning):
        return ({"model": model, "positive": conditioning, "negative": None,
                 "cfg": 1.0},)


class TPUCFGGuider:
    """(MODEL, positive, negative, cfg) → GUIDER — the host's CFGGuider."""

    DESCRIPTION = "Classifier-free-guidance guider."
    RETURN_TYPES = ("GUIDER",)
    RETURN_NAMES = ("guider",)
    FUNCTION = "get_guider"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "model": ("MODEL", {}),
            "positive": ("CONDITIONING", {}),
            "negative": ("CONDITIONING", {}),
            "cfg": ("FLOAT", {"default": 7.5, "min": 1.0, "max": 30.0}),
        }}

    def get_guider(self, model, positive, negative, cfg: float):
        return ({"model": model, "positive": positive, "negative": negative,
                 "cfg": float(cfg)},)


class TPUDisableNoise:
    """→ NOISE that generates zeros — the host's DisableNoise: stage 2+ of a
    split-sigma graph continues from an already-noised latent, so the wired
    LATENT must pass through unchanged (zeros noise + noise_scaling keeps the
    init as the base)."""

    DESCRIPTION = "Zero-noise source for split-sigma continuation stages."
    RETURN_TYPES = ("NOISE",)
    RETURN_NAMES = ("noise",)
    FUNCTION = "get_noise"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {}}

    def get_noise(self):
        return ({"seed": None},)


class TPUSplitSigmas:
    """(SIGMAS, step) → (SIGMAS, SIGMAS) — the host's SplitSigmas: the ladder
    cut at ``step`` with the boundary sigma shared, so running the high half
    then the low half (with DisableNoise) reproduces the unsplit run."""

    DESCRIPTION = "Split a sigma ladder for multi-stage sampling."
    RETURN_TYPES = ("SIGMAS", "SIGMAS")
    RETURN_NAMES = ("high_sigmas", "low_sigmas")
    FUNCTION = "split"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "sigmas": ("SIGMAS", {}),
            "step": ("INT", {"default": 0, "min": 0, "max": 10000}),
        }}

    def split(self, sigmas, step: int):
        return (sigmas[: step + 1], sigmas[step:])


class TPUFlipSigmas:
    """SIGMAS → SIGMAS reversed — the host's FlipSigmas (unsampling graphs);
    a leading zero is bumped to a tiny value so samplers never divide by a
    zero starting sigma."""

    DESCRIPTION = "Reverse a sigma ladder (unsampling)."
    RETURN_TYPES = ("SIGMAS",)
    RETURN_NAMES = ("sigmas",)
    FUNCTION = "flip"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"sigmas": ("SIGMAS", {})}}

    def flip(self, sigmas):
        import jax.numpy as jnp

        flipped = jnp.flip(sigmas, axis=0)
        # Host-faithful: ONLY an exact-zero start is bumped (a small nonzero
        # start from a truncated ladder is preserved).
        return (flipped.at[0].set(
            jnp.where(flipped[0] == 0.0, 1e-4, flipped[0])
        ),)


class TPUSamplerCustomAdvanced:
    """(NOISE, GUIDER, SAMPLER, SIGMAS, LATENT) → (LATENT, LATENT) — the
    host's SamplerCustomAdvanced: the custom-sampling execution node that
    exported FLUX workflows drive instead of the one-box KSampler. The wired
    LATENT is always the noising base (host noise_scaling: a zero EmptyLatent
    degenerates to pure noise; a VAE-encoded one + truncated SIGMAS is
    img2img). The second output mirrors the host's ``denoised_output``; on a
    terminal (σ→0) schedule the two coincide exactly, and this node returns
    the same array for both (divergence only for partial sigma ranges)."""

    DESCRIPTION = "Custom-sampling driver (noise + guider + sampler + sigmas)."
    RETURN_TYPES = ("LATENT", "LATENT")
    RETURN_NAMES = ("output", "denoised_output")
    FUNCTION = "sample"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "noise": ("NOISE", {}),
                "guider": ("GUIDER", {}),
                "sampler": ("SAMPLER", {}),
                "sigmas": ("SIGMAS", {}),
                "latent_image": ("LATENT", {}),
            },
            "optional": {
                "compile_loop": ("BOOLEAN", {"default": False}),
            },
        }

    def sample(self, noise, guider, sampler, sigmas, latent_image,
               compile_loop: bool = False):
        import jax
        import jax.numpy as jnp

        from .sampling.runner import run_sampler

        model = guider["model"]
        positive, negative = guider["positive"], guider.get("negative")
        cfg = guider.get("cfg", 1.0)
        shape = latent_image["samples"].shape
        seed = noise["seed"]
        rng = seed_key(0 if seed is None else seed)
        # DisableNoise (seed None) wires zeros: noise_scaling then keeps the
        # latent as the base — the split-sigma continuation contract.
        noise_arr = (
            jnp.zeros(shape, jnp.float32) if seed is None
            else jax.random.normal(rng, shape, jnp.float32)
        )
        model_cfg, context, pooled, uncond_context, uncond_kwargs, cond_extra = (
            _prepare_sampling_inputs(model, positive, negative, latent_image,
                                     rng=rng)
        )
        model = _model_with_control(
            model, _collect_control(positive), inpaint=positive.get("inpaint"),
            i2v=positive.get("i2v"),
        )
        prediction = getattr(model_cfg, "prediction", "eps")
        out = run_sampler(
            model, noise_arr, context,
            sampler=sampler["sampler"],
            **cond_extra,
            steps=max(1, len(sigmas) - 1),
            sigmas=sigmas,
            cfg_scale=cfg,
            uncond_context=uncond_context,
            uncond_kwargs=uncond_kwargs,
            rng=rng,
            guidance=positive.get("guidance"),
            prediction=prediction,
            init_latent=latent_image["samples"],
            latent_mask=latent_image.get("noise_mask"),
            compile_loop=compile_loop,
            **({} if pooled is None else {"y": pooled}),
        )
        # Host inverse_noise_scaling: a PARTIAL flow run (split sigmas, final
        # σ > 0) stores its output un-interpolated, so the next stage's
        # (1−σ)·latent noise_scaling restores the in-flight state exactly;
        # terminal runs (σ→0) are untouched. eps inverse scaling is identity.
        s_last = float(sigmas[-1])
        if prediction == "flow" and s_last > 0:
            if s_last >= 1.0:
                # σ_last = 1 means pure noise: 1/(1−σ) is infinite. The host
                # divides anyway and silently emits inf (its unsampling graphs
                # hit this); reject loudly instead — documented divergence.
                raise ValueError(
                    "flow sigma ladder ends at 1.0 (pure noise): the partial-"
                    "run inverse noise scaling 1/(1-sigma) is undefined there. "
                    "Split or flip the ladder so the final sigma is below 1."
                )
            out = out / (1.0 - s_last)
        return ({"samples": out}, {"samples": out})


class TPUControlNetLoader:
    """ControlNet checkpoint file → CONTROL_NET wire. The base-UNet family is
    sniffed off the checkpoint (context width / label_emb) unless the caller
    passes one of the UNet families explicitly."""

    DESCRIPTION = "Load an SD-family ControlNet (family sniffed)."
    RETURN_TYPES = ("CONTROL_NET",)
    RETURN_NAMES = ("control_net",)
    FUNCTION = "load"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "ckpt_path": ("STRING", {"default": "",
                                         "tooltip": "safetensors path"}),
            }
        }

    def load(self, ckpt_path: str):
        from .models import load_controlnet_checkpoint

        return ({"model": load_controlnet_checkpoint(ckpt_path)},)


class TPUControlNetApply:
    """Tag a conditioning with ControlNet guidance: the sampler nodes compose
    the control trunk into the MODEL for the run (one jit program; see
    models/controlnet.apply_control), so the residuals condition every model
    call — cond and uncond alike, the host's behavior. ``image`` is the hint
    in pixels (8x the latent grid); ``start_percent``/``end_percent`` gate by
    sampling progress."""

    DESCRIPTION = "Apply a ControlNet hint image to conditioning."
    RETURN_TYPES = ("CONDITIONING",)
    RETURN_NAMES = ("conditioning",)
    FUNCTION = "apply"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "conditioning": ("CONDITIONING", {}),
                "control_net": ("CONTROL_NET", {}),
                "image": ("IMAGE", {}),
                "strength": ("FLOAT", {"default": 1.0, "min": 0.0,
                                       "max": 10.0, "step": 0.01}),
            },
            "optional": {
                "start_percent": ("FLOAT", {"default": 0.0, "min": 0.0,
                                            "max": 1.0, "step": 0.001}),
                "end_percent": ("FLOAT", {"default": 1.0, "min": 0.0,
                                          "max": 1.0, "step": 0.001}),
            },
        }

    def apply(self, conditioning, control_net, image, strength: float = 1.0,
              start_percent: float = 0.0, end_percent: float = 1.0):
        import jax.numpy as jnp

        img = jnp.asarray(image)
        if img.ndim == 3:
            img = img[None]
        spec = {
            "model": control_net["model"],
            "hint": img,
            "strength": float(strength),
            "start_percent": float(start_percent),
            "end_percent": float(end_percent),
        }
        # Chained Apply nodes STACK (residuals sum, the host's
        # multi-controlnet accumulation) — a tuple on the wire.
        prior = conditioning.get("control") or ()
        prior = prior if isinstance(prior, (list, tuple)) else (prior,)
        return ({**conditioning, "control": tuple(prior) + (spec,)},)


class TPUInpaintModelConditioning:
    """(positive, negative, VAE, pixels, mask) → the wire trio that drives a
    DEDICATED inpainting checkpoint (family sd15-inpaint/sdxl-inpaint, 9 input
    channels): conditioning tagged with the latent-space mask + masked-image
    latent (the sampler composes them into the model input via
    ``apply_inpaint_conditioning``), plus the encoded source latent. ``mask``
    is 1 where content regenerates, pixel resolution; masked pixels neutralize
    to 0.5 gray before encoding (the checkpoint's training convention).
    ``noise_mask=True`` additionally pins the keep region each step (the
    latent-noise-mask mechanism — matching host behavior)."""

    DESCRIPTION = "Conditioning + latents for dedicated inpainting checkpoints."
    RETURN_TYPES = ("CONDITIONING", "CONDITIONING", "LATENT")
    RETURN_NAMES = ("positive", "negative", "latent")
    FUNCTION = "encode"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "positive": ("CONDITIONING", {}),
                "negative": ("CONDITIONING", {}),
                "vae": ("VAE", {}),
                "pixels": ("IMAGE", {}),
                "mask": ("MASK", {}),
            },
            "optional": {
                "noise_mask": ("BOOLEAN", {"default": True}),
            },
        }

    def encode(self, positive, negative, vae, pixels, mask,
               noise_mask: bool = True):
        import jax
        import jax.numpy as jnp

        from .models.vae import images_to_vae_input, normalize_mask

        px = images_to_vae_input(pixels)
        m = normalize_mask(mask, px.shape[1:3])
        # Neutralize the regenerate region to 0.5 gray pre-encode (the
        # inpainting checkpoints' training convention). px is already in the
        # VAE's [-1, 1] input space, where 0.5-gray is 0.0.
        masked_px = px * (1.0 - m)
        masked_latent = vae.encode(masked_px, None)
        latent = vae.encode(px, None)
        lat_mask = jax.image.resize(
            m, (m.shape[0], *latent.shape[1:3], 1), method="nearest"
        )
        tag = {"mask": lat_mask, "masked_latent": masked_latent}
        out_latent = {"samples": latent}
        if noise_mask:
            out_latent["noise_mask"] = lat_mask
        return (
            {**positive, "inpaint": tag},
            {**negative, "inpaint": tag},
            out_latent,
        )


class TPUUpscaleModelLoader:
    """ESRGAN-family upscaler checkpoint → UPSCALE_MODEL wire (nf/nb/gc/scale
    sniffed; both public key layouts accepted — models/upscale.py)."""

    DESCRIPTION = "Load an ESRGAN-family (RRDBNet) image upscaler."
    RETURN_TYPES = ("UPSCALE_MODEL",)
    RETURN_NAMES = ("upscale_model",)
    FUNCTION = "load"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "ckpt_path": ("STRING", {"default": "",
                                         "tooltip": "safetensors path"}),
            }
        }

    def load(self, ckpt_path: str):
        from .models import load_upscale_checkpoint

        return (load_upscale_checkpoint(ckpt_path),)


class TPUImageUpscaleWithModel:
    """(UPSCALE_MODEL, IMAGE) → model-upscaled IMAGE; large images process as
    overlapping tiles blended linearly (bounded activation memory)."""

    DESCRIPTION = "Upscale images with an ESRGAN-family model (tiled)."
    RETURN_TYPES = ("IMAGE",)
    RETURN_NAMES = ("image",)
    FUNCTION = "upscale"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "upscale_model": ("UPSCALE_MODEL", {}),
                "image": ("IMAGE", {}),
            },
            "optional": {
                "tile": ("INT", {"default": 512, "min": 64, "max": 4096,
                                 "tooltip": "tile size for large images"}),
            },
        }

    def upscale(self, upscale_model, image, tile: int = 512):
        from .models import upscale_image

        return (upscale_image(upscale_model, image, tile=tile),)


NODE_CLASS_MAPPINGS = {
    "ParallelAnything": ParallelAnything,
    "ParallelAnythingAdvanced": ParallelAnythingAdvanced,
    "ParallelDevice": ParallelDevice,
    "ParallelDeviceList": ParallelDeviceList,
    "TPUCheckpointLoader": TPUCheckpointLoader,
    "TPUCLIPLoader": TPUCLIPLoader,
    "TPUTextEncode": TPUTextEncode,
    "TPUConditioningCombine": TPUConditioningCombine,
    "TPUEmptyLatent": TPUEmptyLatent,
    "TPUVAEEncode": TPUVAEEncode,
    "TPUSetLatentNoiseMask": TPUSetLatentNoiseMask,
    "TPULatentUpscale": TPULatentUpscale,
    "TPUEmptyVideoLatent": TPUEmptyVideoLatent,
    "TPUKSampler": TPUKSampler,
    "TPUKSamplerAdvanced": TPUKSamplerAdvanced,
    "TPUVAEDecode": TPUVAEDecode,
    "TPUSaveImage": TPUSaveImage,
    "TPULoadImage": TPULoadImage,
    "TPUImageScale": TPUImageScale,
    "TPURandomNoise": TPURandomNoise,
    "TPUKSamplerSelect": TPUKSamplerSelect,
    "TPUBasicScheduler": TPUBasicScheduler,
    "TPUFluxGuidance": TPUFluxGuidance,
    "TPUBasicGuider": TPUBasicGuider,
    "TPUCFGGuider": TPUCFGGuider,
    "TPUSamplerCustomAdvanced": TPUSamplerCustomAdvanced,
    "TPUDisableNoise": TPUDisableNoise,
    "TPUSplitSigmas": TPUSplitSigmas,
    "TPUFlipSigmas": TPUFlipSigmas,
    "TPUControlNetLoader": TPUControlNetLoader,
    "TPUControlNetApply": TPUControlNetApply,
    "TPUUpscaleModelLoader": TPUUpscaleModelLoader,
    "TPUImageUpscaleWithModel": TPUImageUpscaleWithModel,
    "TPUInpaintModelConditioning": TPUInpaintModelConditioning,
}

NODE_DISPLAY_NAME_MAPPINGS = {
    "ParallelAnything": "Parallel Anything (True Multi-Device TPU)",
    "ParallelAnythingAdvanced": "Parallel Anything (Advanced: FSDP/TP)",
    "ParallelDevice": "Parallel Device Config",
    "ParallelDeviceList": "Parallel Device List (1-4x)",
    "TPUCheckpointLoader": "Load Checkpoint (TPU)",
    "TPUCLIPLoader": "Load Text Encoder (TPU)",
    "TPUTextEncode": "Text Encode (TPU)",
    "TPUSaveImage": "Save Image (TPU)",
    "TPULoadImage": "Load Image (TPU)",
    "TPUImageScale": "Image Scale (TPU)",
    "TPUConditioningCombine": "Conditioning Combine (TPU, SDXL/FLUX)",
    "TPUEmptyLatent": "Empty Latent (TPU)",
    "TPUVAEEncode": "VAE Encode (TPU)",
    "TPUSetLatentNoiseMask": "Set Latent Noise Mask (TPU)",
    "TPULatentUpscale": "Latent Upscale (TPU)",
    "TPUEmptyVideoLatent": "Empty Video Latent (TPU, WAN)",
    "TPUKSampler": "KSampler (TPU)",
    "TPUKSamplerAdvanced": "KSampler Advanced (TPU)",
    "TPUVAEDecode": "VAE Decode (TPU)",
    "TPURandomNoise": "Random Noise (TPU)",
    "TPUKSamplerSelect": "KSampler Select (TPU)",
    "TPUBasicScheduler": "Basic Scheduler (TPU)",
    "TPUFluxGuidance": "Flux Guidance (TPU)",
    "TPUBasicGuider": "Basic Guider (TPU)",
    "TPUCFGGuider": "CFG Guider (TPU)",
    "TPUSamplerCustomAdvanced": "Sampler Custom Advanced (TPU)",
    "TPUDisableNoise": "Disable Noise (TPU)",
    "TPUSplitSigmas": "Split Sigmas (TPU)",
    "TPUFlipSigmas": "Flip Sigmas (TPU)",
    "TPUControlNetLoader": "Load ControlNet (TPU)",
    "TPUControlNetApply": "Apply ControlNet (TPU)",
    "TPUUpscaleModelLoader": "Load Upscale Model (TPU)",
    "TPUImageUpscaleWithModel": "Upscale Image With Model (TPU)",
    "TPUInpaintModelConditioning": "Inpaint Model Conditioning (TPU)",
}

# Stock-ComfyUI class-name shims (CheckpointLoaderSimple, CLIPTextEncode,
# KSampler, …) so exported API-format workflows resolve unchanged — see
# nodes_compat.py. setdefault-merged: native names always win.
from . import nodes_compat as _compat  # noqa: E402  (needs the classes above)

_compat.register(NODE_CLASS_MAPPINGS, NODE_DISPLAY_NAME_MAPPINGS)
