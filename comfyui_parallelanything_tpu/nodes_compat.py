"""Stock-ComfyUI node-name compatibility shims.

Workflows exported from a stock ComfyUI install reference the builtin node
class names — ``CheckpointLoaderSimple``, ``CLIPTextEncode``, ``KSampler``,
``VAEDecode``, … — not this package's ``TPU*`` names. The reference node pack
runs *inside* ComfyUI and gets those builtins for free
(any_device_parallel.py:1473-1483 registers only its own nodes); this package
hosts the graph itself (host.py), so builtin-name coverage is part of the
parity surface: with these shims an exported API-format workflow runs
unchanged.

Each shim is a thin adapter over the corresponding ``TPU*`` node: it renames
stock input keys (``latent_image``→``latent``, ``samples``→``latent``,
``pixels``→``image``), resolves bare file names against the ComfyUI directory
layout (``$PA_MODELS_DIR/checkpoints`` etc.), and sniffs what stock nodes
leave implicit (the model family, via ``models.loader.sniff_model_family``).
Custom-sampling nodes (RandomNoise, BasicScheduler, SamplerCustomAdvanced, …)
were already built with stock-matching input names and alias directly.

File resolution env vars (the stand-ins for ComfyUI's folder_paths):

- ``PA_MODELS_DIR``  (default ``models``): ``checkpoints/``, ``clip/``,
  ``vae/``, ``loras/`` subdirs are searched, then the dir itself, then the
  bare name as a path.
- ``PA_INPUT_DIR``   (default ``input``): ``LoadImage`` names.
- ``PA_TOKENIZER_JSON`` / ``PA_CLIP_VOCAB`` + ``PA_CLIP_MERGES``: tokenizer
  tables for CLIP towers extracted from bundled checkpoints (checkpoints
  carry encoder weights but never tokenizer data).
- ``PA_T5_TOKENIZER_JSON``: tokenizer for the T5/UMT5 tower
  (``DualCLIPLoader``).
- ``PA_QWEN_TOKENIZER_JSON``: Qwen's byte-level BPE ``tokenizer.json`` for the
  Qwen3 tower (``CLIPLoader`` type ``lumina2``: Z-Image) and the Qwen2.5-VL
  tower (``CLIPLoader`` type ``qwen_image``).
"""

from __future__ import annotations

import os

CATEGORY = "TPU-ParallelAnything/compat"


def _models_dir() -> str:
    return os.environ.get("PA_MODELS_DIR", "models")


def resolve_model_file(name: str, *subdirs: str) -> str:
    """A stock widget's bare file name → an existing path, searched through
    the ComfyUI folder layout; falls back to the name itself (absolute paths
    and cwd-relative paths keep working)."""
    root = _models_dir()
    for sub in subdirs:
        cand = os.path.join(root, sub, name)
        if os.path.exists(cand):
            return cand
    cand = os.path.join(root, name)
    if os.path.exists(cand):
        return cand
    return name


def _clip_tokenizer(max_len: int = 77, pad_id: int | None = None):
    """CLIP BPE tokenizer from env-configured tables, or None (checkpoints
    bundle encoder weights but never tokenizer data — the error surfaces at
    encode time with instructions, not at load time)."""
    tok_json = os.environ.get("PA_TOKENIZER_JSON", "")
    vocab = os.environ.get("PA_CLIP_VOCAB", "")
    merges = os.environ.get("PA_CLIP_MERGES", "")
    from .utils.tokenizer import CLIPBPETokenizer, load_tokenizer_json

    if tok_json:
        return load_tokenizer_json(tok_json, max_len=max_len)
    if vocab and merges:
        return CLIPBPETokenizer.from_files(
            vocab, merges, max_len=max_len, pad_id=pad_id
        )
    return None


_TOKENIZER_HELP = (
    "checkpoints bundle text-encoder weights but never tokenizer tables; set "
    "PA_TOKENIZER_JSON (a tokenizer.json) or PA_CLIP_VOCAB + PA_CLIP_MERGES "
    "(vocab.json + merges.txt), or wire a TPUCLIPLoader node instead"
)


# Key prefixes of the two CLIP towers an SD3-family ``*_incl_clips`` single
# file bundles (CLIP-L, OpenCLIP-bigG), in that order.
SD3_BUNDLED_TOWERS = (
    "text_encoders.clip_l.transformer.",
    "text_encoders.clip_g.transformer.",
)


class CheckpointLoaderSimple:
    """Stock loader: (ckpt_name) → (MODEL, CLIP, VAE). Family is sniffed off
    the checkpoint keys (stock has no family widget); CLIP comes from the
    bundled ``cond_stage_model``/``conditioner`` towers for the SD families
    (SDXL gets the dual L+G wire TPUTextEncode combines) and the
    ``text_encoders.clip_l``/``clip_g`` towers of an SD3-family
    ``*_incl_clips`` file (the ``sd3-triple`` wire, no T5)."""

    DESCRIPTION = "Stock-name checkpoint loader (family sniffed, bundled CLIP)."
    RETURN_TYPES = ("MODEL", "CLIP", "VAE")
    RETURN_NAMES = ("model", "clip", "vae")
    FUNCTION = "load"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"ckpt_name": ("STRING", {"default": ""})}}

    def load(self, ckpt_name: str):
        from .models.loader import peek_safetensors, sniff_model_family
        from .nodes import TPUCheckpointLoader

        path = resolve_model_file(ckpt_name, "checkpoints")
        # Family sniffing needs only key names + two shapes: peek the header
        # instead of materializing a multi-GB file twice (the full read
        # happens once, inside TPUCheckpointLoader).
        family = sniff_model_family(peek_safetensors(path))
        model, vae = TPUCheckpointLoader().load(ckpt_path=path, family=family)
        # Source tag: the LoraLoader shim re-bakes from the original file
        # (LoRA applies to the checkpoint layout pre-conversion). `source`
        # is a plain DiffusionModel field (api.py) — ordinary assignment.
        model.source = {"path": path, "family": family}
        # source_ckpt marks this CLIP wire as rebuildable-from-checkpoint: the
        # LoraLoader shim's strength_clip rebuild must never clobber a wire
        # that came from DualCLIPLoader/TPUCLIPLoader instead.
        clip = {**self._bundled_clip(path, family), "source_ckpt": path}
        return model, clip, vae

    @staticmethod
    def _te_filtered(loras, *prefixes: str):
        """Per-tower text-encoder LoRA sub-stacks: keep only keys under the
        given kohya tower prefixes (te1 = CLIP-L, te2 = OpenCLIP-G) so a
        dual-tower LoRA can never bake its G deltas into the L tower via the
        suffix-match fallback."""
        from .models.loader import load_safetensors

        out = []
        for src, strength in loras or ():
            if strength == 0.0:
                continue
            sd = src if isinstance(src, dict) else load_safetensors(src)
            sub = {k: v for k, v in sd.items() if k.startswith(prefixes)}
            if sub:
                out.append((sub, strength))
        return out

    def _bundled_clip(self, path, family: str, te_loras=None):
        from .models import load_clip_text_checkpoint
        from .models.loader import load_safetensors_subset

        def error_wire(msg: str):
            return {"encoder": None, "tokenizer": None, "type": "error",
                    "tokenizer_error": msg}

        def stamp(ckpt_path, *parts):
            """Content model key for the cross-request embed cache
            (models/embed_cache.py): file identity (path+size+mtime — an
            in-place checkpoint replacement changes the key) + tower tag.
            LoRA-baked towers carry user deltas a file-derived key cannot
            see — they fall back to the cache's per-object lifetime token
            instead (None here)."""
            if te_loras:
                return None
            import hashlib

            from .models.embed_cache import file_stamp

            return hashlib.md5(
                repr((file_stamp(ckpt_path),) + parts).encode()
            ).hexdigest()

        def lg_pair(prefix_l, prefix_g, tag_l, tag_g):
            """The CLIP-L + bigG pair SDXL and the SD3 family bundle, as the
            ``l`` / ``g`` sub-wires of a dual wire; ``None`` when the file
            lacks either tower. bigG is read in whichever layout it is in
            (OpenCLIP resblocks or HF)."""
            from .models import open_clip_g_config

            towers = load_safetensors_subset(path, prefix_l, prefix_g)
            sub_l, sub_g = (
                {k: v for k, v in towers.items() if k.startswith(pfx)}
                for pfx in (prefix_l, prefix_g)
            )
            if not sub_l or not sub_g:
                return None
            if te_loras:
                from .models.convert import bake_lora

                # kohya dual-tower convention: te1 = CLIP-L, te2 = G.
                for sub, s in self._te_filtered(
                    te_loras, "lora_te1_", "lora_te_"
                ):
                    sub_l = bake_lora(sub_l, sub, s)
                for sub, s in self._te_filtered(te_loras, "lora_te2_"):
                    sub_g = bake_lora(sub_g, sub, s)
            enc_l = load_clip_text_checkpoint(sub_l)
            enc_g = load_clip_text_checkpoint(
                sub_g, cfg=open_clip_g_config(),
                open_clip=any(
                    k.endswith("positional_embedding") for k in sub_g
                ),
            )
            tok_l = _clip_tokenizer(max_len=enc_l.cfg.max_len)
            tok_g = _clip_tokenizer(max_len=enc_g.cfg.max_len, pad_id=0)
            err = None if (tok_l and tok_g) else _TOKENIZER_HELP

            def wire(enc, tok, tag):
                return {"encoder": enc, "tokenizer": tok, "type": "clip",
                        "model_key": stamp(path, family, tag),
                        "tokenizer_error": err}

            return {"l": wire(enc_l, tok_l, tag_l),
                    "g": wire(enc_g, tok_g, tag_g), "tokenizer_error": err}

        try:
            if family in ("sd15", "sd21", "sd21-v", "sd21-unclip"):
                open_clip = family.startswith("sd21")
                cfg = None
                if open_clip:
                    from .models import open_clip_h_config

                    cfg = open_clip_h_config()
                tower = load_safetensors_subset(path, "cond_stage_model.")
                if not tower:
                    return error_wire(
                        "checkpoint has no bundled cond_stage_model tower; "
                        "wire a TPUCLIPLoader node instead"
                    )
                if te_loras:
                    from .models.convert import bake_lora

                    for sub, s in self._te_filtered(
                        te_loras, "lora_te_", "lora_te1_"
                    ):
                        tower = bake_lora(tower, sub, s)
                enc = load_clip_text_checkpoint(
                    tower, cfg=cfg, open_clip=open_clip
                )
                tok = _clip_tokenizer(
                    max_len=enc.cfg.max_len, pad_id=0 if open_clip else None
                )
                return {
                    "encoder": enc, "tokenizer": tok, "type": "clip",
                    "model_key": stamp(path, family, "cond_stage_model"),
                    "tokenizer_error": None if tok else _TOKENIZER_HELP,
                }
            if family == "sdxl-refiner":
                from .models import open_clip_g_config

                # The refiner bundles ONE tower: OpenCLIP-G under
                # conditioner.embedders.0.model.* (no CLIP-L). A plain
                # G-tower CLIP wire — CLIPTextEncodeSDXLRefiner consumes it
                # directly.
                tower = load_safetensors_subset(path, "conditioner.embedders.0.")
                if not tower:
                    return error_wire(
                        "sdxl-refiner checkpoint has no bundled conditioner "
                        "tower; wire TPUCLIPLoader type=open-clip-g instead"
                    )
                if te_loras:
                    from .models.convert import bake_lora

                    for sub, s in self._te_filtered(te_loras, "lora_te2_",
                                                    "lora_te_"):
                        tower = bake_lora(tower, sub, s)
                enc_g = load_clip_text_checkpoint(
                    tower, cfg=open_clip_g_config(), open_clip=True
                )
                tok_g = _clip_tokenizer(max_len=enc_g.cfg.max_len, pad_id=0)
                return {
                    "encoder": enc_g, "tokenizer": tok_g, "type": "clip",
                    "model_key": stamp(path, family, "conditioner.0"),
                    "tokenizer_error": None if tok_g else _TOKENIZER_HELP,
                }
            if family == "sdxl":
                # conditioner.embedders.0 = CLIP-L (HF layout),
                # conditioner.embedders.1 = OpenCLIP-G (resblocks layout).
                pair = lg_pair("conditioner.embedders.0.",
                               "conditioner.embedders.1.",
                               "embedders.0", "embedders.1")
                if pair is None:
                    return error_wire(
                        "sdxl checkpoint has no bundled conditioner towers; "
                        "wire TPUCLIPLoader nodes instead"
                    )
                return {"type": "sdxl-dual", **pair}
            if family in ("sd3-medium", "sd35-medium", "sd35-large"):
                # The ``*_incl_clips`` single files: CLIP-L and bigG under
                # text_encoders.clip_l / clip_g (bigG in the HF layout), no
                # T5 — the documented low-memory deployment; the encode path
                # then conditions on the 77 CLIP tokens alone.
                pair = lg_pair(*SD3_BUNDLED_TOWERS, "clip_l", "clip_g")
                if pair is not None:
                    return {"type": "sd3-triple", **pair, "t5": None}
            return error_wire(
                f"{family} checkpoints do not bundle text encoders; wire "
                "TPUCLIPLoader (or the DualCLIPLoader shim) instead"
            )
        except Exception as e:  # noqa: BLE001 — degrade to an encode-time error
            return error_wire(f"bundled text-encoder extraction failed: {e}")


class DualCLIPLoader:
    """Stock dual loader (FLUX/SD3 workflows): two encoder files → one CLIP
    wire. ``type=flux`` pairs T5-XXL (context) with CLIP-L (pooled)."""

    DESCRIPTION = "Stock-name dual text-encoder loader (flux/sdxl/sd3 pairs)."
    RETURN_TYPES = ("CLIP",)
    RETURN_NAMES = ("clip",)
    FUNCTION = "load"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "clip_name1": ("STRING", {"default": ""}),
                "clip_name2": ("STRING", {"default": ""}),
                "type": (["flux", "sdxl", "sd3"], {"default": "flux"}),
            }
        }

    def load(self, clip_name1: str, clip_name2: str, type: str = "flux"):
        from .nodes import TPUCLIPLoader

        loader = TPUCLIPLoader()

        def clip_wire(name: str, encoder_type: str, **kw):
            path = resolve_model_file(name, "clip", "text_encoders")
            if encoder_type in ("t5", "umt5"):
                tok_json = os.environ.get("PA_T5_TOKENIZER_JSON", "")
                if not tok_json:
                    raise ValueError(
                        "DualCLIPLoader t5 tower needs PA_T5_TOKENIZER_JSON "
                        "(no vocab/merges form exists for T5 tokenizers)"
                    )
                kw["tokenizer_json"] = tok_json
            else:
                tok_json = os.environ.get("PA_TOKENIZER_JSON", "")
                if tok_json:
                    kw["tokenizer_json"] = tok_json
                else:
                    kw["vocab_path"] = os.environ.get("PA_CLIP_VOCAB", "")
                    kw["merges_path"] = os.environ.get("PA_CLIP_MERGES", "")
            (wire,) = loader.load(path, encoder_type, **kw)
            return wire

        if type == "flux":
            # Stock convention: name1 = t5xxl, name2 = clip_l. A "t5" in
            # either file name corrects swapped wiring; with no match in
            # either, trust the positional convention (a rename like
            # flan_xxl.safetensors must not flip a correctly-ordered graph).
            n1 = os.path.basename(clip_name1).lower()
            n2 = os.path.basename(clip_name2).lower()
            swapped = "t5" not in n1 and "t5" in n2
            t5_name = clip_name2 if swapped else clip_name1
            l_name = clip_name1 if swapped else clip_name2
            # The source's conditioner (BFL ``HFEmbedder``, schnell): T5 at
            # 256 tokens, padded with id 0 and handed NO attention mask, so
            # the padded keys take part in every softmax.
            return (
                {
                    "type": "flux-dual",
                    "t5": {**clip_wire(t5_name, "t5", max_len=256),
                           "attention_mask": False},
                    "l": clip_wire(l_name, "clip-l"),
                    "tokenizer_error": None,
                },
            )
        if type == "sdxl":
            return (
                {
                    "type": "sdxl-dual",
                    "l": clip_wire(clip_name1, "clip-l"),
                    "g": clip_wire(clip_name2, "open-clip-g"),
                    "tokenizer_error": None,
                },
            )
        # type == "sd3": the two-tower form of the SD3 conditioning. Stock
        # detects which two of {clip_l, clip_g, t5xxl} were supplied from the
        # state dicts themselves, so the common clip_l+t5xxl / clip_g+t5xxl
        # pairings load correctly — classify both files (name markers, then
        # safetensors key signature) and leave the absent tower None; the
        # encode path zero-fills it like stock's SD3 CLIP. Files that defy
        # classification fall back to the positional (clip_l, clip_g)
        # convention, one per free CLIP slot.
        kinds = []
        for name in (clip_name1, clip_name2):
            path = resolve_model_file(name, "clip", "text_encoders")
            kinds.append(_classify_text_tower(name, path))
        if kinds[0] is not None and kinds[0] == kinds[1]:
            raise ValueError(
                f"DualCLIPLoader type=sd3 got two {kinds[0]} files "
                f"({clip_name1!r} and {clip_name2!r}); it needs two "
                "DIFFERENT towers of clip_l/clip_g/t5xxl"
            )
        for slot in ("clip-l", "open-clip-g"):
            if slot not in kinds and None in kinds:
                kinds[kinds.index(None)] = slot
        towers = dict(zip(kinds, (clip_name1, clip_name2)))
        wire_of = {
            "clip-l": ("l", "clip-l"),
            "open-clip-g": ("g", "open-clip-g"),
            "t5": ("t5", "t5"),
        }
        out = {"type": "sd3-triple", "l": None, "g": None, "t5": None,
               "tokenizer_error": None}
        for kind, name in towers.items():
            key, encoder_type = wire_of[kind]
            out[key] = clip_wire(name, encoder_type)
        return (out,)


class CLIPLoader:
    """Stock single-tower text-encoder loader: (clip_name, type) → CLIP.
    The ``type`` menu names the model family the tower serves; the tower
    architecture resolves from it (plus a t5-in-filename sniff for the
    families whose templates ship either tower). Tokenizer tables come from
    the PA_* env vars like the DualCLIPLoader shim. Host-provided builtin
    (any_device_parallel.py:1473-1483)."""

    DESCRIPTION = "Stock-name single text-encoder loader."
    RETURN_TYPES = ("CLIP",)
    RETURN_NAMES = ("clip",)
    FUNCTION = "load"
    CATEGORY = CATEGORY

    # Stock type menu → tower architecture. Families needing two towers
    # (flux/sdxl dual) still load their single named file here — stock wires
    # two CLIPLoaders or one DualCLIPLoader interchangeably.
    _TYPE_TOWER = {
        "stable_diffusion": "clip-l",
        "sdxl": "clip-l",
        "sd3": "clip-l",
        "flux": "clip-l",
        "stable_cascade": "clip-l",
        "wan": "umt5",
        "ltxv": "t5",
        "pixart": "t5",
        "cosmos": "t5",
        # The type ComfyUI's Z-Image template loads its tower with: which
        # tower the file holds is read off its keys (``load``).
        "lumina2": None,
        # ComfyUI's Qwen-Image template: the Qwen2.5-VL tower, checked against
        # the file's keys likewise.
        "qwen_image": None,
        "hunyuan_video": "clip-l",
    }

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "clip_name": ("STRING", {"default": ""}),
                "type": (sorted(cls._TYPE_TOWER),
                         {"default": "stable_diffusion"}),
            },
            "optional": {
                "device": (["default", "cpu"], {"default": "default"}),
            },
        }

    def load(self, clip_name: str, type: str = "stable_diffusion",
             device: str = "default"):
        from .nodes import TPUCLIPLoader

        if type not in self._TYPE_TOWER:
            raise ValueError(
                f"CLIPLoader type {type!r} is not supported — one of "
                f"{sorted(self._TYPE_TOWER)}"
            )
        tower = self._TYPE_TOWER[type]
        name = os.path.basename(clip_name).lower()
        path = resolve_model_file(clip_name, "clip", "text_encoders")
        if tower is None:
            tower = _classify_text_tower("", path)
            want, what = {
                "lumina2": ("qwen3", "Qwen3 tower (model.layers.N.self_attn.q_norm); "
                                     "Z-Image's qwen_3_4b"),
                "qwen_image": ("qwen25vl", "Qwen2.5-VL language model (model.layers.N."
                                           "self_attn.q_proj.bias, no q_norm); "
                                           "Qwen-Image's qwen_2.5_vl_7b"),
            }[type]
            if tower != want:
                raise ValueError(
                    f"CLIPLoader type={type!r}: {clip_name!r} holds no {what} "
                    "is the tower this type loads here"
                )
        elif "umt5" in name:
            tower = "umt5"
        elif "t5" in name:
            tower = "t5" if tower not in ("umt5",) else tower
        kw = {}
        if tower in ("qwen3", "qwen25vl"):
            tok_json = os.environ.get("PA_QWEN_TOKENIZER_JSON", "")
            if not tok_json:
                raise ValueError(
                    f"CLIPLoader type={type!r} loads a Qwen tower and needs "
                    "PA_QWEN_TOKENIZER_JSON (Qwen's byte-level BPE "
                    "tokenizer.json)"
                )
            kw["tokenizer_json"] = tok_json
        elif tower in ("t5", "umt5"):
            tok_json = os.environ.get("PA_T5_TOKENIZER_JSON", "")
            if not tok_json:
                raise ValueError(
                    f"CLIPLoader type={type!r} loads a T5-family tower and "
                    "needs PA_T5_TOKENIZER_JSON (no vocab/merges form exists)"
                )
            kw["tokenizer_json"] = tok_json
            # Stock T5 token budgets: WAN tokenizes umt5 at 512, the other
            # t5-served families at 256 — the CLIP default of 77 would
            # silently truncate typical video prompts.
            kw["max_len"] = 512 if type == "wan" else 256
        else:
            tok_json = os.environ.get("PA_TOKENIZER_JSON", "")
            if tok_json:
                kw["tokenizer_json"] = tok_json
            else:
                kw["vocab_path"] = os.environ.get("PA_CLIP_VOCAB", "")
                kw["merges_path"] = os.environ.get("PA_CLIP_MERGES", "")
        (wire,) = TPUCLIPLoader().load(path, tower, **kw)
        return (wire,)


def _classify_text_tower(name: str, path: str | None = None) -> str | None:
    """Which tower a text-encoder file holds: ``t5`` / ``open-clip-g`` /
    ``clip-l`` / ``qwen3`` / ``qwen25vl`` (the last two by key signature only). Filename markers first (the stock SD3 template ships
    clip_l/clip_g/t5xxl); unresolved names fall back to the safetensors key
    signature (header-only — no tensor reads except one embedding shape)."""
    n = os.path.basename(name).lower()
    if "t5" in n:
        return "t5"
    if "clip_g" in n or "clipg" in n:
        return "open-clip-g"
    if "clip_l" in n or "clipl" in n:
        return "clip-l"
    if not path or not os.path.isfile(path):
        return None
    try:
        from safetensors import safe_open

        with safe_open(path, framework="numpy") as f:
            keys = set(f.keys())
            if any(k.endswith("layers.0.self_attn.q_norm.weight") for k in keys):
                return "qwen3"
            if any(k.endswith("layers.0.mlp.gate_proj.weight") for k in keys) and any(
                    k.endswith("layers.0.self_attn.q_proj.bias") for k in keys):
                # a SwiGLU decoder with Qwen2's biases on q / k / v and no
                # q/k norm (CLIP's HF layout has such biases and no gate)
                return "qwen25vl"
            if any(k.startswith("encoder.block.") for k in keys) \
                    or "shared.weight" in keys:
                return "t5"
            # open-clip layout: top-level token_embedding + text_projection.
            if "token_embedding.weight" in keys:
                return "open-clip-g"
            for k in keys:
                if k.endswith("token_embedding.weight"):
                    width = f.get_slice(k).get_shape()[1]
                    return "open-clip-g" if width >= 1024 else "clip-l"
    except Exception:
        return None
    return None


class TripleCLIPLoader:
    """Stock triple text-encoder loader (the SD3/SD3.5 templates): clip_l +
    clip_g + t5xxl files → ONE CLIP wire carrying all three towers. Encoding
    that wire assembles SD3's (context, y) — L⊕G penultimate streams padded
    to 4096 and sequence-concatenated with the T5 stream, y = pooled L⊕G
    (``models.text_encoders.sd3_text_conditioning``). Files are matched to
    towers by name markers, then by key signature — stock's widget order
    carries no typed meaning. Host-provided builtin
    (any_device_parallel.py:1473-1483)."""

    DESCRIPTION = "Stock-name triple text-encoder loader (SD3: L + G + T5)."
    RETURN_TYPES = ("CLIP",)
    RETURN_NAMES = ("clip",)
    FUNCTION = "load"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "clip_name1": ("STRING", {"default": ""}),
                "clip_name2": ("STRING", {"default": ""}),
                "clip_name3": ("STRING", {"default": ""}),
            }
        }

    def load(self, clip_name1: str, clip_name2: str, clip_name3: str):
        from .nodes import TPUCLIPLoader

        names = [clip_name1, clip_name2, clip_name3]
        paths = [resolve_model_file(n, "clip", "text_encoders") for n in names]
        towers: dict[str, str] = {}
        for name, path in zip(names, paths):
            kind = _classify_text_tower(name, path)
            if kind is None:
                raise ValueError(
                    f"TripleCLIPLoader cannot tell which tower {name!r} holds "
                    "— name it with a clip_l/clip_g/t5 marker"
                )
            if kind in towers:
                raise ValueError(
                    f"TripleCLIPLoader got two {kind} files ({towers[kind]!r} "
                    f"and {name!r}); it needs one each of clip_l/clip_g/t5"
                )
            towers[kind] = path
        missing = {"clip-l", "open-clip-g", "t5"} - set(towers)
        if missing:
            raise ValueError(
                f"TripleCLIPLoader is missing {sorted(missing)} towers "
                f"(classified: { {k: os.path.basename(v) for k, v in towers.items()} })"
            )

        loader = TPUCLIPLoader()

        def clip_wire(path: str, encoder_type: str):
            kw = {}
            if encoder_type == "t5":
                tok_json = os.environ.get("PA_T5_TOKENIZER_JSON", "")
                if not tok_json:
                    raise ValueError(
                        "TripleCLIPLoader t5 tower needs PA_T5_TOKENIZER_JSON "
                        "(no vocab/merges form exists for T5 tokenizers)"
                    )
                kw["tokenizer_json"] = tok_json
                # Stock SD3 tokenizes T5 at 77 tokens to match the CLIP
                # streams' sequence budget — the default already fits.
            else:
                tok_json = os.environ.get("PA_TOKENIZER_JSON", "")
                if tok_json:
                    kw["tokenizer_json"] = tok_json
                else:
                    kw["vocab_path"] = os.environ.get("PA_CLIP_VOCAB", "")
                    kw["merges_path"] = os.environ.get("PA_CLIP_MERGES", "")
            (wire,) = loader.load(path, encoder_type, **kw)
            return wire

        return (
            {
                "type": "sd3-triple",
                "l": clip_wire(towers["clip-l"], "clip-l"),
                "g": clip_wire(towers["open-clip-g"], "open-clip-g"),
                "t5": clip_wire(towers["t5"], "t5"),
                "tokenizer_error": None,
            },
        )


class VAELoader:
    """Stock external-VAE loader: (vae_name) → VAE. Resolves through
    $PA_MODELS_DIR/vae; the file's key layout picks the family — WAN's causal
    3D video VAE (``encoder.downsamples``/``decoder.upsamples`` flat
    Sequentials; Qwen-Image's autoencoder is that architecture and those keys,
    and ``VAEDecode`` takes its 4-D image latent through the decoder's
    one-frame path) vs the AutoencoderKL image families (sniffed by
    sniff_vae_config: latent width, SDXL scaling). Host-provided builtin
    (any_device_parallel.py:1473-1483)."""

    DESCRIPTION = "Stock-name external VAE loader (image + WAN video layouts)."
    RETURN_TYPES = ("VAE",)
    RETURN_NAMES = ("vae",)
    FUNCTION = "load"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"vae_name": ("STRING", {"default": ""})}}

    def load(self, vae_name: str):
        from .models.loader import (
            load_vae_checkpoint,
            load_wan_vae_checkpoint,
            peek_safetensors,
        )

        path = resolve_model_file(vae_name, "vae")
        if not os.path.isfile(path):
            raise ValueError(
                f"VAE file not found: {vae_name!r} (searched "
                "$PA_MODELS_DIR/vae and the name as a path)"
            )
        keys = peek_safetensors(path)
        if any("decoder.upsamples." in k for k in keys):
            return (load_wan_vae_checkpoint(path),)
        return (load_vae_checkpoint(path),)


class UNETLoader:
    """Stock diffusion-model-only loader (FLUX/WAN templates): (unet_name,
    weight_dtype) → MODEL. Family is sniffed off the keys like
    CheckpointLoaderSimple, and a FLUX file's block counts with it (a depth
    cut loads at the depth it has: ``load_flux_checkpoint``); ``weight_dtype``
    is accepted for workflow compatibility but ignored — the load path's
    dtype policy (bf16 compute, FLUX kernels resident in bf16, fp8
    upcast-on-load, mirroring the reference's fp8 handling at
    any_device_parallel.py:93-124) already covers every menu entry."""

    DESCRIPTION = "Stock-name bare diffusion-model loader (family sniffed)."
    RETURN_TYPES = ("MODEL",)
    RETURN_NAMES = ("model",)
    FUNCTION = "load_unet"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "unet_name": ("STRING", {"default": ""}),
                "weight_dtype": (
                    ["default", "fp8_e4m3fn", "fp8_e4m3fn_fast", "fp8_e5m2"],
                    {"default": "default"},
                ),
            }
        }

    def load_unet(self, unet_name: str, weight_dtype: str = "default"):
        from .models.loader import peek_safetensors, sniff_model_family
        from .nodes import TPUCheckpointLoader

        path = resolve_model_file(
            unet_name, "diffusion_models", "unet", "checkpoints"
        )
        family = sniff_model_family(peek_safetensors(path))
        model, _ = TPUCheckpointLoader().load(
            ckpt_path=path, family=family, load_vae=False
        )
        # Same source tag CheckpointLoaderSimple leaves: the LoraLoader shims
        # re-bake from the original file.
        model.source = {"path": path, "family": family}
        return (model,)


class unCLIPConditioning:  # noqa: N801 — stock node name
    """Stock unCLIP node: tags the conditioning with the CLIP image embeds +
    noise-augmentation level; the sampler assembles the model's adm vector
    from the tags (models/unet.unclip_adm — host SD21UNCLIP.encode_adm
    semantics: q_sample augmentation, level embedding, strength weighting,
    multi-tag merge). Chained nodes stack tags. Host-provided builtin
    (any_device_parallel.py:1473-1483)."""

    DESCRIPTION = "Stock-name unCLIP image conditioning (SD2.x-unCLIP)."
    RETURN_TYPES = ("CONDITIONING",)
    RETURN_NAMES = ("conditioning",)
    FUNCTION = "apply_adm"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "conditioning": ("CONDITIONING", {}),
                "clip_vision_output": ("CLIP_VISION_OUTPUT", {}),
                "strength": ("FLOAT", {"default": 1.0, "min": -10.0,
                                       "max": 10.0, "step": 0.01}),
                "noise_augmentation": ("FLOAT", {"default": 0.0, "min": 0.0,
                                                 "max": 1.0, "step": 0.01}),
            }
        }

    def apply_adm(self, conditioning, clip_vision_output, strength: float,
                  noise_augmentation: float):
        tag = {
            "embeds": clip_vision_output["image_embeds"],
            "strength": float(strength),
            "noise_augmentation": float(noise_augmentation),
        }
        return (
            {
                **conditioning,
                "unclip": tuple(conditioning.get("unclip", ())) + (tag,),
            },
        )


class LoraLoader:
    """Stock LoRA node: (MODEL, CLIP, lora_name, strengths) → patched
    (MODEL, CLIP). LoRA bakes into the checkpoint layout BEFORE conversion
    (models/convert.bake_lora — the reference's patches-then-load order,
    any_device_parallel.py:971-1004), so this shim re-loads the tagged source
    checkpoint with the LoRA applied. Chained LoraLoaders STACK: each link
    appends to the accumulated ``(path, strength)`` list carried on the source
    tag and the whole stack re-bakes in chain order. ``strength_clip`` bakes
    the LoRA's text-encoder deltas (kohya ``lora_te*`` keys) into the bundled
    CLIP towers the same way — the returned CLIP wire is rebuilt from the
    source checkpoint when the LoRA carries te keys and strength_clip ≠ 0."""

    DESCRIPTION = "Stock-name LoRA loader (re-bakes from the source checkpoint)."
    RETURN_TYPES = ("MODEL", "CLIP")
    RETURN_NAMES = ("model", "clip")
    FUNCTION = "load_lora"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "model": ("MODEL", {}),
                "clip": ("CLIP", {}),
                "lora_name": ("STRING", {"default": ""}),
                "strength_model": (
                    "FLOAT", {"default": 1.0, "min": -4.0, "max": 4.0}
                ),
                "strength_clip": (
                    "FLOAT", {"default": 1.0, "min": -4.0, "max": 4.0}
                ),
            }
        }

    def load_lora(self, model, clip, lora_name: str,
                  strength_model: float = 1.0, strength_clip: float = 1.0):
        from .nodes import TPUCheckpointLoader

        source = getattr(model, "source", None)
        if source is not None and source.get("merged"):
            raise ValueError(
                "LoRA-after-merge is not supported: LoRA baking re-converts "
                "from the source checkpoint file, and a merged model has "
                "none — apply LoraLoader to each input model BEFORE "
                "ModelMergeSimple instead"
            )
        if source is None or not source.get("path"):
            raise ValueError(
                "LoraLoader needs a MODEL from CheckpointLoaderSimple (the "
                "source-checkpoint tag); for TPUCheckpointLoader models pass "
                "lora_path on the loader itself"
            )
        lora = resolve_model_file(lora_name, "loras")
        # An empty/missing name must not silently return an unpatched model
        # (TPUCheckpointLoader treats lora_path="" as no-LoRA).
        if not lora_name or not os.path.isfile(lora):
            raise ValueError(
                f"LoRA file not found: {lora_name!r} (searched "
                f"$PA_MODELS_DIR/loras and the name as a path)"
            )
        model_stack = list(source.get("loras", ())) + [(lora, strength_model)]
        patched, _ = TPUCheckpointLoader().load(
            ckpt_path=source["path"], family=source["family"],
            lora_path=model_stack,
            load_vae=False,  # re-bake only needs the diffusion model
        )
        clip_stack = list(source.get("te_loras", ())) + [(lora, strength_clip)]
        patched.source = {**source, "loras": model_stack,
                          "te_loras": clip_stack}
        patched.lora_delegate = self._lane_delegate(model, patched)
        clip = self._maybe_rebake_clip(clip, source, clip_stack)
        return patched, clip

    @staticmethod
    def _lane_delegate(model, patched):
        """The serving-tier twin of this bake: ``{"base", "factors"}`` when
        the whole bake recovers as exact low-rank factors against the
        unpatched base (models/lora.factorize_bake — SVD of the per-leaf
        delta, which works on the CONVERTED layout's head-split/renamed
        leaves where checkpoint-keyed extraction cannot). The continuous-
        batching scheduler then buckets LoRA prompts on the base model and
        carries the factors as per-lane state (one shared program for any
        LoRA mix), while inline legs keep the bake. None (= bake only)
        whenever any delta is unrepresentable — a partial factor map would
        make the served result diverge from the bake. Chained links resolve
        against the base-most model, so a LoRA stack is still ONE delegate."""
        from .models.lora import factorize_bake

        import jax

        base = (getattr(model, "lora_delegate", None) or {}).get("base", model)
        if not isinstance(getattr(base, "params", None), dict) \
                or not isinstance(getattr(patched, "params", None), dict):
            return None
        if any(leaf.dtype.itemsize < 4 and leaf.ndim >= 2
               for leaf in jax.tree.leaves(patched.params)):
            # A bake rounded into 16-bit resident kernels (convert.resident)
            # is not low-rank against its base any more: the delta carries
            # the rounding of every element. No exact factors, so bake only.
            return None
        from .models.loader import residency

        residency.ensure(base.params)  # the delta is taken from the base's tensors
        factors = factorize_bake(base.params, patched.params)
        return {"base": base, "factors": factors} if factors else None

    @staticmethod
    def _maybe_rebake_clip(clip, source: dict, clip_stack: list):
        """Rebuild the CLIP wire with text-encoder LoRA deltas baked — only
        when there is anything to bake (te keys present at nonzero clip
        strength, checked from safetensors HEADERS before any tensor data is
        read) and only for wires that actually came from this checkpoint's
        bundled towers (``source_ckpt`` tag): an externally-loaded CLIP
        (DualCLIPLoader) must never be clobbered by a rebuild."""
        from .models.loader import load_safetensors, peek_safetensors
        from .utils.logging import get_logger

        te_prefixes = ("lora_te_", "lora_te1_", "lora_te2_")
        active = [
            (p, s) for p, s in clip_stack
            if s != 0.0 and any(
                k.startswith(te_prefixes) for k in peek_safetensors(p)
            )
        ]
        if not active:
            return clip
        if not isinstance(clip, dict) or clip.get("source_ckpt") != source["path"]:
            get_logger().warning(
                "LoraLoader strength_clip: the CLIP wire did not come from "
                "this checkpoint's bundled towers (DualCLIPLoader/TPUCLIPLoader"
                ") — text-encoder LoRA deltas are NOT baked; bake them into "
                "the encoder files offline if needed"
            )
            return clip
        # Each active file loads ONCE per link; _bundled_clip's per-tower
        # passes reuse the in-memory dicts (the source tag keeps paths, not
        # multi-MB state dicts).
        loaded = [(load_safetensors(p), s) for p, s in active]
        rebuilt = CheckpointLoaderSimple()._bundled_clip(
            source["path"], source["family"], te_loras=loaded
        )
        # Preserve wire state the chain added upstream (CLIPSetLastLayer's
        # clip_skip tag, source_ckpt itself, etc.): stock patches the incoming
        # clip object, so everything but the freshly-baked encoder fields must
        # survive.
        extra_state = {
            k: v for k, v in clip.items()
            if k not in rebuilt and k not in ("encoder", "tokenizer")
        }
        return {**rebuilt, **extra_state}


class LoraLoaderModelOnly:
    """Stock model-only LoRA link (the stock FLUX LoRA templates): same
    re-bake-from-source semantics as LoraLoader (the reference's
    bake-before-replicate order, any_device_parallel.py:971-1004) with no
    CLIP wire — ``strength_clip`` is fixed at 0 so the text towers are
    untouched."""

    DESCRIPTION = "Stock-name model-only LoRA loader."
    RETURN_TYPES = ("MODEL",)
    RETURN_NAMES = ("model",)
    FUNCTION = "load_lora_model_only"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "model": ("MODEL", {}),
                "lora_name": ("STRING", {"default": ""}),
                "strength_model": (
                    "FLOAT", {"default": 1.0, "min": -4.0, "max": 4.0}
                ),
            }
        }

    def load_lora_model_only(self, model, lora_name: str,
                             strength_model: float = 1.0):
        patched, _ = LoraLoader().load_lora(
            model, None, lora_name, strength_model, strength_clip=0.0
        )
        return (patched,)


class CLIPSetLastLayer:
    """Stock clip-skip node: tags the CLIP wire; TPUTextEncode honors the tag
    when its own clip_skip widget is 0 (host stop_at_clip_layer semantics:
    -1 = final layer, -2 = penultimate)."""

    DESCRIPTION = "Stock-name clip-skip (tags the CLIP wire)."
    RETURN_TYPES = ("CLIP",)
    RETURN_NAMES = ("clip",)
    FUNCTION = "set_last_layer"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "clip": ("CLIP", {}),
                "stop_at_clip_layer": ("INT", {"default": -1, "min": -24, "max": -1}),
            }
        }

    def set_last_layer(self, clip, stop_at_clip_layer: int):
        if stop_at_clip_layer not in (-1, -2):
            raise ValueError(
                "only stop_at_clip_layer -1 (final) or -2 (penultimate) is "
                f"supported, got {stop_at_clip_layer}"
            )
        return ({**clip, "clip_skip": -stop_at_clip_layer},)


def _renamed(tpu_cls, rename: dict[str, str], *, name: str):
    """Adapter class factory: stock input keys → TPU node keys."""

    class Shim:
        DESCRIPTION = f"Stock-name alias of {tpu_cls.__name__}."
        RETURN_TYPES = tpu_cls.RETURN_TYPES
        RETURN_NAMES = getattr(tpu_cls, "RETURN_NAMES", None)
        FUNCTION = "run"
        CATEGORY = CATEGORY

        @classmethod
        def INPUT_TYPES(cls):
            spec = tpu_cls.INPUT_TYPES()
            back = {v: k for k, v in rename.items()}
            return {
                section: {back.get(k, k): v for k, v in entries.items()}
                for section, entries in spec.items()
            }

        def run(self, **kwargs):
            mapped = {rename.get(k, k): v for k, v in kwargs.items()}
            inner = tpu_cls()
            return getattr(inner, tpu_cls.FUNCTION)(**mapped)

    Shim.__name__ = Shim.__qualname__ = name
    return Shim


class LoadImage:
    """Stock image loader: names resolve against ``$PA_INPUT_DIR``."""

    DESCRIPTION = "Stock-name alias of TPULoadImage (input-dir resolution)."
    FUNCTION = "run"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"image": ("STRING", {"default": ""})}}

    def run(self, image: str):
        from .nodes import TPULoadImage

        base = os.environ.get("PA_INPUT_DIR", "input")
        cand = os.path.join(base, image)
        return TPULoadImage().load(cand if os.path.exists(cand) else image)

    # RETURN_TYPES mirror the TPU node (set below to avoid import cycles).


class LatentUpscale:
    """Stock latent upscale takes absolute target pixel dims; the TPU node
    takes scale factors — computed here from the wired latent at runtime,
    height and width independently. ``crop`` is accepted and ignored
    (center-crop after resize is a stock nicety, not a parity requirement —
    documented divergence)."""

    DESCRIPTION = "Stock-name latent upscale (absolute dims → scale factor)."
    RETURN_TYPES = ("LATENT",)
    RETURN_NAMES = ("latent",)
    FUNCTION = "upscale"
    CATEGORY = CATEGORY

    _METHODS = {
        "nearest-exact": "nearest", "nearest": "nearest",
        "bilinear": "bilinear", "area": "bilinear",
        "bicubic": "bicubic", "bislerp": "bicubic",
    }

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "samples": ("LATENT", {}),
                "upscale_method": (list(cls._METHODS), {"default": "bilinear"}),
                "width": ("INT", {"default": 1024, "min": 16, "max": 16384}),
                "height": ("INT", {"default": 1024, "min": 16, "max": 16384}),
            },
            "optional": {"crop": ("STRING", {"default": "disabled"})},
        }

    def upscale(self, samples, upscale_method: str, width: int, height: int,
                crop: str = "disabled"):
        from .nodes import TPULatentUpscale

        z = samples["samples"]
        h, w = z.shape[-3], z.shape[-2]
        # Stock dims are pixel-space; latents are 8x smaller. Height and
        # width scale independently (aspect-changing upscales resize exactly
        # to the stock target).
        scale_h = max(height // 8, 2) / h
        scale_w = max(width // 8, 2) / w
        method = self._METHODS.get(upscale_method, "bilinear")
        return TPULatentUpscale().upscale(
            samples, scale_h, method, scale_w=scale_w
        )


class _EmptyLatent16ch:
    """Stock EmptySD3LatentImage: 16-channel latents (SD3/FLUX), no channel
    widget."""

    DESCRIPTION = "Stock-name 16-channel empty latent (SD3/FLUX)."
    RETURN_TYPES = ("LATENT",)
    RETURN_NAMES = ("latent",)
    FUNCTION = "generate"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "width": ("INT", {"default": 1024, "min": 16, "max": 16384}),
                "height": ("INT", {"default": 1024, "min": 16, "max": 16384}),
                "batch_size": ("INT", {"default": 1, "min": 1, "max": 4096}),
            }
        }

    def generate(self, width: int, height: int, batch_size: int = 1):
        from .nodes import TPUEmptyLatent

        return TPUEmptyLatent().generate(
            width=width, height=height, batch_size=batch_size, channels=16
        )


class UpscaleModelLoader:
    """Stock loader: model_name resolves via $PA_MODELS_DIR/upscale_models."""

    DESCRIPTION = "Stock-name upscale-model loader (folder-layout resolution)."
    RETURN_TYPES = ("UPSCALE_MODEL",)
    RETURN_NAMES = ("upscale_model",)
    FUNCTION = "load_model"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"model_name": ("STRING", {"default": ""})}}

    def load_model(self, model_name: str):
        from .nodes import TPUUpscaleModelLoader

        path = resolve_model_file(model_name, "upscale_models")
        if not model_name or not os.path.isfile(path):
            raise ValueError(
                f"upscale model not found: {model_name!r} (searched "
                "$PA_MODELS_DIR/upscale_models and the name as a path)"
            )
        return TPUUpscaleModelLoader().load(ckpt_path=path)


class CLIPVisionLoader:
    """Stock loader: clip_name resolves via $PA_MODELS_DIR/clip_vision; the
    tower (ViT-L/H/bigG) is sniffed off the HF-layout checkpoint
    (models/vision.py)."""

    DESCRIPTION = "Stock-name CLIP vision loader (tower sniffed)."
    RETURN_TYPES = ("CLIP_VISION",)
    RETURN_NAMES = ("clip_vision",)
    FUNCTION = "load_clip"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"clip_name": ("STRING", {"default": ""})}}

    def load_clip(self, clip_name: str):
        from .models.vision import load_clip_vision_checkpoint

        path = resolve_model_file(clip_name, "clip_vision")
        if not clip_name or not os.path.isfile(path):
            raise ValueError(
                f"CLIP vision model not found: {clip_name!r} (searched "
                "$PA_MODELS_DIR/clip_vision and the name as a path)"
            )
        return ({"model": load_clip_vision_checkpoint(path)},)


class CLIPVisionEncode:
    """Stock encode: IMAGE → CLIP_VISION_OUTPUT (projected image_embeds, RAW
    last_hidden — post_layernorm applies only to the pooled CLS, the HF
    convention — and the raw penultimate hidden states). Preprocessing is the
    host's clip_preprocess (bicubic short-side resize + center crop + CLIP
    normalization); ``crop`` "none" squashes to the square instead."""

    DESCRIPTION = "Stock-name CLIP vision encode."
    RETURN_TYPES = ("CLIP_VISION_OUTPUT",)
    RETURN_NAMES = ("clip_vision_output",)
    FUNCTION = "encode"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "clip_vision": ("CLIP_VISION", {}),
                "image": ("IMAGE", {}),
            },
            "optional": {
                "crop": (["center", "none"], {"default": "center"}),
            },
        }

    def encode(self, clip_vision, image, crop: str = "center"):
        from .models.vision import clip_preprocess

        model = clip_vision["model"]
        px = clip_preprocess(
            image, size=model.cfg.image_size, crop=(crop != "none")
        )
        embeds, last, penultimate = model(px)
        return ({
            "image_embeds": embeds,
            "last_hidden": last,
            "penultimate": penultimate,
        },)


class WanImageToVideo:
    """Stock WAN i2v entry node: allocates the empty video latent and tags
    BOTH conditionings with the i2v conditioning the sampler composes into
    the model (nodes._model_with_control → models.wan.apply_i2v_conditioning):
    a 4-channel latent frame mask ‖ the VAE-encoded start frames
    (channel-concat, the WAN2.2 contract) plus, when ``clip_vision_output``
    is wired, the CLIP-vision penultimate states for WAN2.1-style
    checkpoints' img_emb branch. The stock node's
    concat_latent_image/concat_mask/clip_vision_output conditioning keys
    collapse into the single ``i2v`` tag here. Host-provided builtin
    (any_device_parallel.py:1473-1483 registers only the pack's own nodes)."""

    DESCRIPTION = "Stock-name WAN image→video conditioning + empty latent."
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
                "width": ("INT", {"default": 832, "min": 16, "max": 8192,
                                  "step": 16}),
                "height": ("INT", {"default": 480, "min": 16, "max": 8192,
                                   "step": 16}),
                "length": ("INT", {"default": 81, "min": 1, "max": 1024,
                                   "step": 4}),
                "batch_size": ("INT", {"default": 1, "min": 1, "max": 16}),
            },
            "optional": {
                "clip_vision_output": ("CLIP_VISION_OUTPUT", {}),
                "start_image": ("IMAGE", {}),
            },
        }

    def encode(self, positive, negative, vae, width: int, height: int,
               length: int, batch_size: int, start_image=None,
               clip_vision_output=None):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from .models.vae import images_to_vae_input

        t_lat = vae.cfg.latent_frames(length)  # validates the 4k+1 schedule
        f = vae.spatial_factor
        zc = vae.cfg.z_channels
        latent = {
            "samples": jnp.zeros(
                (batch_size, t_lat, height // f, width // f, zc)
            )
        }
        tag: dict = {}
        if start_image is not None:
            img = jnp.asarray(start_image)
            if img.ndim == 3:
                img = img[None]
            F = min(img.shape[0], length)
            img = img[:F]
            if img.shape[1:3] != (height, width):
                img = jax.image.resize(
                    img, (F, height, width, img.shape[-1]), method="bilinear"
                )
            clip = jnp.concatenate(
                [
                    images_to_vae_input(img)[None],  # frames of ONE clip
                    jnp.zeros((1, length - F, height, width, img.shape[-1])),
                ],
                axis=1,
            )
            cond_latent = vae.encode(clip)
            h, w = cond_latent.shape[2], cond_latent.shape[3]
            # Frame mask: channel c of latent frame j marks the pixel frame it
            # folds — frame 0 fills all 4 channels of latent frame 0 (the
            # causal VAE's lone first frame, repeated like stock's msk
            # repeat), latent frame j≥1 channel c folds pixel 4(j-1)+1+c.
            mask = np.zeros((1, t_lat, h, w, 4), np.float32)
            for j in range(t_lat):
                for c in range(4):
                    pix = 0 if j == 0 else 4 * (j - 1) + 1 + c
                    if pix < F:
                        mask[:, j, :, :, c] = 1.0
            tag["cond"] = jnp.concatenate(
                [jnp.asarray(mask), cond_latent], axis=-1
            )
        if clip_vision_output is not None:
            tag["clip_fea"] = clip_vision_output["penultimate"]
        if tag:
            positive = {**positive, "i2v": tag}
            negative = {**negative, "i2v": tag}
        return positive, negative, latent


class ControlNetLoader:
    """Stock loader: control_net_name resolves via $PA_MODELS_DIR/controlnet."""

    DESCRIPTION = "Stock-name ControlNet loader (folder-layout resolution)."
    RETURN_TYPES = ("CONTROL_NET",)
    RETURN_NAMES = ("control_net",)
    FUNCTION = "load_controlnet"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"control_net_name": ("STRING", {"default": ""})}}

    def load_controlnet(self, control_net_name: str):
        from .nodes import TPUControlNetLoader

        path = resolve_model_file(control_net_name, "controlnet")
        if not control_net_name or not os.path.isfile(path):
            raise ValueError(
                f"ControlNet file not found: {control_net_name!r} (searched "
                "$PA_MODELS_DIR/controlnet and the name as a path)"
            )
        return TPUControlNetLoader().load(ckpt_path=path)


class ControlNetApply:
    """Stock apply: (conditioning, control_net, image, strength). The control
    trunk composes into the MODEL at sampling (one jit program), conditioning
    cond AND uncond calls — the host's semantics."""

    DESCRIPTION = "Stock-name ControlNet apply."
    RETURN_TYPES = ("CONDITIONING",)
    RETURN_NAMES = ("conditioning",)
    FUNCTION = "apply_controlnet"
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
            }
        }

    def apply_controlnet(self, conditioning, control_net, image,
                         strength: float = 1.0):
        from .nodes import TPUControlNetApply

        return TPUControlNetApply().apply(
            conditioning, control_net, image, strength
        )


class ControlNetApplyAdvanced:
    """Stock advanced apply: (positive, negative, control_net, image,
    strength, start_percent, end_percent) → (positive, negative). The control
    tag rides the positive; because the sampler composes control into the
    MODEL itself, the negative's calls are conditioned identically (stock
    applies the same control to both — same net effect, one tag)."""

    DESCRIPTION = "Stock-name ControlNet apply (strength window)."
    RETURN_TYPES = ("CONDITIONING", "CONDITIONING")
    RETURN_NAMES = ("positive", "negative")
    FUNCTION = "apply_controlnet"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "positive": ("CONDITIONING", {}),
                "negative": ("CONDITIONING", {}),
                "control_net": ("CONTROL_NET", {}),
                "image": ("IMAGE", {}),
                "strength": ("FLOAT", {"default": 1.0, "min": 0.0,
                                       "max": 10.0, "step": 0.01}),
                "start_percent": ("FLOAT", {"default": 0.0, "min": 0.0,
                                            "max": 1.0, "step": 0.001}),
                "end_percent": ("FLOAT", {"default": 1.0, "min": 0.0,
                                          "max": 1.0, "step": 0.001}),
            }
        }

    def apply_controlnet(self, positive, negative, control_net, image,
                         strength: float = 1.0, start_percent: float = 0.0,
                         end_percent: float = 1.0):
        from .nodes import TPUControlNetApply

        (tagged,) = TPUControlNetApply().apply(
            positive, control_net, image, strength,
            start_percent=start_percent, end_percent=end_percent,
        )
        return tagged, negative


def _tag_all_entries(conditioning: dict, tag: dict) -> dict:
    """Apply ``tag`` to the primary cond AND every combined extra — stock
    conditioning_set_values maps over every list entry (the one convention
    all the conditioning shims share)."""
    out = {**conditioning, **tag}
    if conditioning.get("extras"):
        out["extras"] = tuple({**e, **tag} for e in conditioning["extras"])
    return out


def _repeat_to_batch(a, batch: int):
    """Stock repeat_to_batch_size: cycle (tile) then truncate, so any source
    batch composites onto any destination batch (larger, smaller, or
    non-divisor alike)."""
    import jax.numpy as jnp

    if a.shape[0] == batch:
        return a
    reps = -(-batch // a.shape[0])
    return jnp.tile(a, (reps,) + (1,) * (a.ndim - 1))[:batch]


class ImageCompositeMasked:
    """Stock masked paste: source composites over destination at (x, y),
    optionally through a mask (1 = take source) — the standard inpaint
    post-step that pastes the regenerated region back into the original."""

    DESCRIPTION = "Stock-name masked image composite."
    RETURN_TYPES = ("IMAGE",)
    RETURN_NAMES = ("image",)
    FUNCTION = "composite"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "destination": ("IMAGE", {}),
                "source": ("IMAGE", {}),
                "x": ("INT", {"default": 0, "min": 0, "max": 16384}),
                "y": ("INT", {"default": 0, "min": 0, "max": 16384}),
                "resize_source": ("BOOLEAN", {"default": False}),
            },
            "optional": {"mask": ("MASK", {})},
        }

    def composite(self, destination, source, x: int, y: int,
                  resize_source: bool = False, mask=None):
        import jax
        import jax.numpy as jnp

        dst = jnp.asarray(destination)
        src = jnp.asarray(source)
        if dst.ndim == 3:
            dst = dst[None]
        if src.ndim == 3:
            src = src[None]
        B, H, W, C = dst.shape
        if resize_source:
            src = jax.image.resize(
                src, (src.shape[0], H, W, C), method="bilinear"
            )
        src = _repeat_to_batch(src, B)
        # Mask normalizes to the FULL source size first, THEN crops with the
        # paste window (stock composite order — squishing the whole mask down
        # to the clipped size would blend edge values instead of cropping).
        if mask is None:
            m_full = jnp.ones((1, *src.shape[1:3], 1), jnp.float32)
        else:
            from .models.vae import normalize_mask

            # Cycle the mask batch to the destination batch like stock's
            # repeat_to_batch_size treatment of source/mask — a mask batch
            # matching neither 1 nor B must not surface as an XLA broadcast
            # error.
            m_full = _repeat_to_batch(normalize_mask(mask, src.shape[1:3]), B)
        # Clip the paste window to the destination bounds.
        h = min(src.shape[1], H - y)
        w = min(src.shape[2], W - x)
        if h <= 0 or w <= 0:
            return (dst,)
        src = src[:, :h, :w, :]
        m = m_full[:, :h, :w, :]
        region = dst[:, y:y + h, x:x + w, :]
        blended = src * m + region * (1.0 - m)
        return (dst.at[:, y:y + h, x:x + w, :].set(blended),)


class LatentComposite:
    """Stock latent paste: samples_from over samples_to at (x, y) — widget
    coordinates are PIXELS, divided by 8 to latent cells like stock."""

    DESCRIPTION = "Stock-name latent composite."
    RETURN_TYPES = ("LATENT",)
    RETURN_NAMES = ("latent",)
    FUNCTION = "composite"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "samples_to": ("LATENT", {}),
                "samples_from": ("LATENT", {}),
                "x": ("INT", {"default": 0, "min": 0, "max": 16384, "step": 8}),
                "y": ("INT", {"default": 0, "min": 0, "max": 16384, "step": 8}),
                "feather": ("INT", {"default": 0, "min": 0, "max": 16384,
                                    "step": 8}),
            }
        }

    def composite(self, samples_to, samples_from, x: int, y: int,
                  feather: int = 0):
        import jax.numpy as jnp

        dst = jnp.asarray(samples_to["samples"])
        src = jnp.asarray(samples_from["samples"])
        xl, yl, fl = x // 8, y // 8, feather // 8
        B, H, W, C = dst.shape
        h = min(src.shape[1], H - yl)
        w = min(src.shape[2], W - xl)
        if h <= 0 or w <= 0:
            return ({**samples_to},)
        src = src[:, :h, :w, :]
        src = _repeat_to_batch(src, B)
        m = jnp.ones((h, w), jnp.float32)
        if fl > 0:
            # Feather ONLY the pasted edges that fall strictly inside the
            # destination — edges flush with the canvas border stay hard
            # (stock gates each ramp the same way).
            ones_h = jnp.ones((h,), jnp.float32)
            ramp_h = jnp.minimum(
                jnp.arange(1, h + 1, dtype=jnp.float32) / fl, 1.0
            )
            top_r = ramp_h if yl > 0 else ones_h
            bot_r = ramp_h[::-1] if yl + h < H else ones_h
            m = m * jnp.minimum(top_r, bot_r)[:, None]
            ones_w = jnp.ones((w,), jnp.float32)
            ramp_w = jnp.minimum(
                jnp.arange(1, w + 1, dtype=jnp.float32) / fl, 1.0
            )
            left_r = ramp_w if xl > 0 else ones_w
            right_r = ramp_w[::-1] if xl + w < W else ones_w
            m = m * jnp.minimum(left_r, right_r)[None, :]
        m = m[None, :, :, None]
        region = dst[:, yl:yl + h, xl:xl + w, :]
        return ({
            **samples_to,
            "samples": dst.at[:, yl:yl + h, xl:xl + w, :].set(
                src * m + region * (1.0 - m)
            ),
        },)


class SaveAnimatedWEBP:
    """Stock video save: a (B|F, H, W, 3) image sequence (e.g. WAN decode
    frames) → one animated WEBP under the served output root."""

    DESCRIPTION = "Stock-name animated WEBP save."
    RETURN_TYPES = ("STRING",)
    RETURN_NAMES = ("paths",)
    FUNCTION = "save_images"
    CATEGORY = CATEGORY
    OUTPUT_NODE = True

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "images": ("IMAGE", {}),
                "filename_prefix": ("STRING", {"default": "ComfyUI"}),
                "fps": ("FLOAT", {"default": 6.0, "min": 0.01, "max": 1000.0}),
                "lossless": ("BOOLEAN", {"default": True}),
                "quality": ("INT", {"default": 80, "min": 0, "max": 100}),
            }
        }

    def save_images(self, images, filename_prefix: str = "ComfyUI",
                    fps: float = 6.0, lossless: bool = True,
                    quality: int = 80):
        import numpy as np
        from PIL import Image

        arr = np.asarray(images)
        if arr.ndim == 3:
            arr = arr[None]
        if arr.ndim == 5:  # (B, F, H, W, 3) video batch → flatten clips
            arr = arr.reshape((-1,) + arr.shape[2:])
        frames = [
            Image.fromarray(
                (np.clip(f, 0.0, 1.0) * 255.0 + 0.5).astype(np.uint8)
            )
            for f in arr
        ]
        # Shared save-path semantics with TPUSaveImage (subfolder prefixes,
        # escape rejection, past-highest-index counter).
        from .nodes import resolve_save_target

        target_dir, name, start = resolve_save_target(
            filename_prefix or "ComfyUI", suffix="webp"
        )
        path = os.path.join(target_dir, f"{name}_{start:05d}.webp")
        frames[0].save(
            path, save_all=True, append_images=frames[1:],
            duration=max(1, int(round(1000.0 / fps))), loop=0,
            lossless=lossless, quality=quality,
        )
        return ((path,),)


class VAEEncodeForInpaint:
    """Stock soft-inpaint encode for REGULAR (4-channel) checkpoints: blanks
    the masked pixels before encoding (so the masked content cannot leak into
    the latent), grows the mask by ``grow_mask_by`` pixels (stock default 6 —
    seam room for the VAE's receptive field), and returns the latent with a
    ``noise_mask`` for the sampler's latent-noise-mask mechanism. Dedicated
    9-channel checkpoints use InpaintModelConditioning instead."""

    DESCRIPTION = "Stock-name inpaint encode (masked latent + noise_mask)."
    RETURN_TYPES = ("LATENT",)
    RETURN_NAMES = ("latent",)
    FUNCTION = "encode"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "vae": ("VAE", {}),
                "pixels": ("IMAGE", {}),
                "mask": ("MASK", {}),
                "grow_mask_by": ("INT", {"default": 6, "min": 0, "max": 64}),
            }
        }

    def encode(self, vae, pixels, mask, grow_mask_by: int = 6):
        import jax
        import jax.numpy as jnp

        from .models.vae import images_to_vae_input, normalize_mask

        px = images_to_vae_input(pixels)
        m = jnp.round(
            jnp.clip(normalize_mask(mask, px.shape[1:3]), 0.0, 1.0)
        )
        # Blank with the ORIGINAL rounded mask (0.0 == 0.5-gray in the VAE's
        # [-1, 1] input space — stock keeps the real-pixel context around the
        # seam); the GROWN mask serves only as the noise_mask.
        latent = vae.encode(px * (1.0 - m), None)
        grown = m
        if grow_mask_by > 1:
            # Stock's grow: a k×k max window (~(k-1)/2 px per side).
            k = int(grow_mask_by)
            grown = jax.lax.reduce_window(
                m, -jnp.inf, jax.lax.max,
                (1, k, k, 1), (1, 1, 1, 1), "SAME",
            )
        lat_mask = jax.image.resize(
            grown, (grown.shape[0], *latent.shape[1:3], 1), method="nearest"
        )
        return ({"samples": latent, "noise_mask": lat_mask},)


class ImagePadForOutpaint:
    """Stock outpaint prep: pad the image by left/top/right/bottom pixels
    (edge-replicated — gives the sampler a color hint) and return the matching
    regenerate mask, feathered ``feathering`` pixels into the original so the
    seam blends."""

    DESCRIPTION = "Stock-name outpaint padding (padded image + feathered mask)."
    RETURN_TYPES = ("IMAGE", "MASK")
    RETURN_NAMES = ("image", "mask")
    FUNCTION = "expand_image"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "image": ("IMAGE", {}),
                "left": ("INT", {"default": 0, "min": 0, "max": 16384,
                                 "step": 8}),
                "top": ("INT", {"default": 0, "min": 0, "max": 16384,
                                "step": 8}),
                "right": ("INT", {"default": 0, "min": 0, "max": 16384,
                                  "step": 8}),
                "bottom": ("INT", {"default": 0, "min": 0, "max": 16384,
                                   "step": 8}),
                "feathering": ("INT", {"default": 40, "min": 0, "max": 16384,
                                       "step": 1}),
            }
        }

    def expand_image(self, image, left: int, top: int, right: int,
                     bottom: int, feathering: int = 40):
        import jax.numpy as jnp

        img = jnp.asarray(image)
        if img.ndim == 3:
            img = img[None]
        B, H, W, C = img.shape
        padded = jnp.pad(
            img, ((0, 0), (top, bottom), (left, right), (0, 0)), mode="edge"
        )
        # Mask: 1 in the new border, feathered down to 0 inside the original.
        rows = jnp.arange(H, dtype=jnp.float32)
        cols = jnp.arange(W, dtype=jnp.float32)
        # Distance to the nearest PADDED edge of the original region; sides
        # without padding don't feather (jnp.inf distance).
        d = jnp.full((H, W), jnp.inf, jnp.float32)
        if top:
            d = jnp.minimum(d, rows[:, None])
        if bottom:
            d = jnp.minimum(d, (H - 1 - rows)[:, None])
        if left:
            d = jnp.minimum(d, cols[None, :])
        if right:
            d = jnp.minimum(d, (W - 1 - cols)[None, :])
        # Stock semantics: QUADRATIC ramp, and no feathering at all when the
        # requested feather would cover most of the image.
        if feathering > 0 and feathering * 2 < H and feathering * 2 < W:
            v = jnp.clip(1.0 - d / float(feathering), 0.0, 1.0)
            inner = v * v
        else:
            inner = jnp.zeros((H, W), jnp.float32)
        mask = jnp.pad(
            inner, ((top, bottom), (left, right)), constant_values=1.0
        )
        return padded, jnp.broadcast_to(mask[None], (B, *mask.shape))


class ConditioningSetTimestepRange:
    """Stock timestep-range gate: scope a conditioning to a sampling-progress
    window (start/end in [0, 1], 0 = first step). Effective on conds riding a
    Combine's ``extras`` (the stock multi-stage pattern: two prompts covering
    different ranges); on a lone PRIMARY cond the gate is ignored with a
    warning at sampling time (a step with no active cond has no stock
    fallback either)."""

    DESCRIPTION = "Stock-name conditioning timestep window."
    RETURN_TYPES = ("CONDITIONING",)
    RETURN_NAMES = ("conditioning",)
    FUNCTION = "set_range"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "conditioning": ("CONDITIONING", {}),
                "start": ("FLOAT", {"default": 0.0, "min": 0.0, "max": 1.0,
                                    "step": 0.001}),
                "end": ("FLOAT", {"default": 1.0, "min": 0.0, "max": 1.0,
                                  "step": 0.001}),
            }
        }

    def set_range(self, conditioning, start: float, end: float):
        return (_tag_all_entries(
            conditioning, {"timestep_range": (float(start), float(end))}
        ),)


class ConditioningZeroOut:
    """Stock zero-out: the FLUX-workflow "negative" — a conditioning whose
    embeddings are all zeros (guidance-distilled models take it instead of a
    real negative prompt)."""

    DESCRIPTION = "Stock-name conditioning zero-out (FLUX negative)."
    RETURN_TYPES = ("CONDITIONING",)
    RETURN_NAMES = ("conditioning",)
    FUNCTION = "zero_out"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"conditioning": ("CONDITIONING", {})}}

    def zero_out(self, conditioning):
        import jax.numpy as jnp

        out = dict(conditioning)
        for k in ("context", "penultimate", "pooled"):
            if out.get(k) is not None:
                out[k] = jnp.zeros_like(out[k])
        if out.get("extras"):
            out["extras"] = tuple(
                {**e, **{k: jnp.zeros_like(e[k])
                         for k in ("context", "pooled")
                         if e.get(k) is not None}}
                for e in out["extras"]
            )
        return (out,)


class CLIPTextEncodeSDXL:
    """Stock SDXL encode: both prompts (text_g/text_l) through the dual
    bundled towers with the full size/crop/target conditioning vector —
    TPUTextEncode's sdxl-dual path generalized to the stock widget surface."""

    DESCRIPTION = "Stock-name SDXL dual-prompt text encode."
    RETURN_TYPES = ("CONDITIONING",)
    RETURN_NAMES = ("conditioning",)
    FUNCTION = "encode"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "clip": ("CLIP", {}),
                "width": ("INT", {"default": 1024, "min": 0, "max": 16384}),
                "height": ("INT", {"default": 1024, "min": 0, "max": 16384}),
                "crop_w": ("INT", {"default": 0, "min": 0, "max": 16384}),
                "crop_h": ("INT", {"default": 0, "min": 0, "max": 16384}),
                "target_width": ("INT", {"default": 1024, "min": 0,
                                         "max": 16384}),
                "target_height": ("INT", {"default": 1024, "min": 0,
                                          "max": 16384}),
                "text_g": ("STRING", {"default": "", "multiline": True}),
                "text_l": ("STRING", {"default": "", "multiline": True}),
            }
        }

    def encode(self, clip, width: int, height: int, crop_w: int, crop_h: int,
               target_width: int, target_height: int,
               text_g: str, text_l: str):
        from .models.text_encoders import sdxl_text_conditioning
        from .nodes import TPUTextEncode

        if clip.get("type") != "sdxl-dual":
            raise ValueError(
                "CLIPTextEncodeSDXL needs the dual L+G CLIP wire "
                "(CheckpointLoaderSimple on an SDXL checkpoint, or "
                "DualCLIPLoader type=sdxl)"
            )
        enc = TPUTextEncode()
        # Honor a CLIPSetLastLayer tag on the dual wire exactly like
        # TPUTextEncode's own sdxl-dual branch: default (0) = penultimate
        # (SDXL's training convention); an explicit skip selects each tower's
        # skip-resolved stream.
        clip_skip = int(clip.get("clip_skip", 0))
        (cl,) = enc.encode(clip["l"], text_l, clip_skip)
        (cg,) = enc.encode(clip["g"], text_g, clip_skip)
        str_l = cl["penultimate"] if clip_skip == 0 else cl["context"]
        str_g = cg["penultimate"] if clip_skip == 0 else cg["context"]
        context, y = sdxl_text_conditioning(
            str_l, str_g, cg["pooled"],
            width=width, height=height, crop_x=crop_w, crop_y=crop_h,
            target_width=target_width, target_height=target_height,
        )
        return ({"context": context, "penultimate": None, "pooled": y},)


class ConditioningCombine:
    """Stock combine: BOTH conditionings apply during sampling. The second
    cond (and any extras it accumulated) rides the first's ``extras`` tuple;
    the sampler blends per-cond predictions area-weight-normalized
    (sampling/k_samplers.EpsDenoiser._combine_conds — ComfyUI's
    calc_cond_batch rule, minus its crop-run optimization)."""

    DESCRIPTION = "Stock-name conditioning combine (both prompts apply)."
    RETURN_TYPES = ("CONDITIONING",)
    RETURN_NAMES = ("conditioning",)
    FUNCTION = "combine"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "conditioning_1": ("CONDITIONING", {}),
                "conditioning_2": ("CONDITIONING", {}),
            }
        }

    def combine(self, conditioning_1, conditioning_2):
        second = {k: v for k, v in conditioning_2.items() if k != "extras"}
        extras = (
            tuple(conditioning_1.get("extras", ()))
            + (second,)
            + tuple(conditioning_2.get("extras", ()))
        )
        return ({**conditioning_1, "extras": extras},)


class ConditioningSetArea:
    """Stock area conditioning: scope a prompt to a latent-space box. Widgets
    are pixels (step 8, like stock); the wire stores latent units (//8)."""

    DESCRIPTION = "Stock-name area conditioning (regional prompting)."
    RETURN_TYPES = ("CONDITIONING",)
    RETURN_NAMES = ("conditioning",)
    FUNCTION = "append"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "conditioning": ("CONDITIONING", {}),
                "width": ("INT", {"default": 64, "min": 8, "max": 16384,
                                  "step": 8}),
                "height": ("INT", {"default": 64, "min": 8, "max": 16384,
                                   "step": 8}),
                "x": ("INT", {"default": 0, "min": 0, "max": 16384, "step": 8}),
                "y": ("INT", {"default": 0, "min": 0, "max": 16384, "step": 8}),
                "strength": ("FLOAT", {"default": 1.0, "min": 0.0, "max": 10.0}),
            }
        }

    def append(self, conditioning, width: int, height: int, x: int, y: int,
               strength: float = 1.0):
        # Stock conditioning_set_values maps over EVERY list entry — primary
        # and combined extras alike get the box. Clears any fractional box
        # (stock keeps one "area" key, later node wins).
        return (_tag_all_entries(conditioning, {
            "area": (height // 8, width // 8, y // 8, x // 8),
            "area_pct": None,
            "strength": float(strength),
        }),)


class ConditioningAverage:
    """Stock average: lerp ``from`` into ``to`` at (1 − strength). Token-wise
    over the overlap; ``to``'s trailing tokens survive unblended and a shorter
    ``from`` is zero-padded — the stock node's exact rule."""

    DESCRIPTION = "Stock-name conditioning average (prompt blending)."
    RETURN_TYPES = ("CONDITIONING",)
    RETURN_NAMES = ("conditioning",)
    FUNCTION = "addWeighted"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "conditioning_to": ("CONDITIONING", {}),
                "conditioning_from": ("CONDITIONING", {}),
                "conditioning_to_strength": (
                    "FLOAT", {"default": 1.0, "min": 0.0, "max": 1.0}
                ),
            }
        }

    def addWeighted(self, conditioning_to, conditioning_from,  # noqa: N802 — stock method name
                    conditioning_to_strength: float):
        import jax.numpy as jnp

        s = float(conditioning_to_strength)
        from_ctx = jnp.asarray(conditioning_from["context"])
        p_from = conditioning_from.get("pooled")

        def blend_one(cond: dict) -> dict:
            to_ctx = jnp.asarray(cond["context"])
            n = to_ctx.shape[1]
            f = from_ctx
            if f.shape[1] < n:
                pad = [(0, 0)] * f.ndim
                pad[1] = (0, n - f.shape[1])
                f = jnp.pad(f, pad)
            out = {**cond, "context": to_ctx * s + f[:, :n] * (1.0 - s)}
            p_to = cond.get("pooled")
            if p_to is not None and p_from is not None:
                out["pooled"] = (jnp.asarray(p_to) * s
                                 + jnp.asarray(p_from) * (1.0 - s))
            return out

        # Stock blends EVERY entry of the to-list — here the primary cond and
        # each combined extra alike.
        out = blend_one(conditioning_to)
        if conditioning_to.get("extras"):
            out["extras"] = tuple(
                blend_one(e) for e in conditioning_to["extras"]
            )
        return (out,)


# Stock upscale_method menu → jax.image.resize method. "area" has no jax
# equivalent; bilinear is the closest downscale behavior (documented
# divergence — stock uses adaptive average pooling there).
_STOCK_RESIZE = {
    "nearest-exact": "nearest",
    "bilinear": "bilinear",
    "area": "bilinear",
    "bicubic": "cubic",
    "lanczos": "lanczos3",
}


def _stock_resize(image, width: int, height: int, upscale_method: str,
                  crop: str = "disabled"):
    """The stock ImageScale core: optional center-crop to the target aspect
    ratio, then resize. Returns a (B, H, W, C) float image in [0, 1]."""
    import jax
    import jax.numpy as jnp

    method = _STOCK_RESIZE.get(upscale_method)
    if method is None:
        raise ValueError(
            f"upscale_method must be one of {sorted(_STOCK_RESIZE)}, "
            f"got {upscale_method!r}"
        )
    img = jnp.asarray(image)
    if img.ndim == 3:
        img = img[None]
    if crop == "center":
        b, h, w, c = img.shape
        aspect = width / height
        if w / h > aspect:  # too wide: crop columns
            new_w = max(1, round(h * aspect))
            x0 = (w - new_w) // 2
            img = img[:, :, x0:x0 + new_w, :]
        elif w / h < aspect:  # too tall: crop rows
            new_h = max(1, round(w / aspect))
            y0 = (h - new_h) // 2
            img = img[:, y0:y0 + new_h, :, :]
    elif crop != "disabled":
        raise ValueError(f"crop must be 'disabled' or 'center', got {crop!r}")
    out = jax.image.resize(
        img, (img.shape[0], height, width, img.shape[-1]), method=method
    )
    return jnp.clip(out, 0.0, 1.0)


class ImageScale:
    """Stock image resize: exact width/height with the stock method menu and
    center-crop option (TPUImageScale is the native sibling with the jax
    method names)."""

    DESCRIPTION = "Stock-name image resize (method menu + center crop)."
    RETURN_TYPES = ("IMAGE",)
    RETURN_NAMES = ("image",)
    FUNCTION = "upscale"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "image": ("IMAGE", {}),
                "upscale_method": (sorted(_STOCK_RESIZE), {"default": "bilinear"}),
                "width": ("INT", {"default": 512, "min": 0, "max": 16384}),
                "height": ("INT", {"default": 512, "min": 0, "max": 16384}),
                "crop": (["disabled", "center"], {"default": "disabled"}),
            }
        }

    def upscale(self, image, upscale_method: str, width: int, height: int,
                crop: str = "disabled"):
        # Stock 0-sentinel: a zero dim derives from the other one keeping the
        # source aspect ratio (both zero is meaningless).
        if width == 0 and height == 0:
            raise ValueError("ImageScale: width and height cannot both be 0")
        if width == 0 or height == 0:
            import jax.numpy as jnp

            img = jnp.asarray(image)
            src_h, src_w = (img.shape[0:2] if img.ndim == 3
                            else img.shape[1:3])
            if width == 0:
                width = max(1, round(height * src_w / src_h))
            else:
                height = max(1, round(width * src_h / src_w))
        return (_stock_resize(image, width, height, upscale_method, crop),)


class ImageScaleBy:
    """Stock relative image resize: scale_by factor, no crop."""

    DESCRIPTION = "Stock-name relative image resize."
    RETURN_TYPES = ("IMAGE",)
    RETURN_NAMES = ("image",)
    FUNCTION = "upscale"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "image": ("IMAGE", {}),
                "upscale_method": (sorted(_STOCK_RESIZE), {"default": "bilinear"}),
                "scale_by": ("FLOAT", {"default": 1.0, "min": 0.01, "max": 8.0,
                                       "step": 0.01}),
            }
        }

    def upscale(self, image, upscale_method: str, scale_by: float):
        import jax.numpy as jnp

        img = jnp.asarray(image)
        if img.ndim == 3:
            img = img[None]
        h = max(1, round(img.shape[1] * scale_by))
        w = max(1, round(img.shape[2] * scale_by))
        return (_stock_resize(img, w, h, upscale_method),)


class PreviewImage:
    """Stock preview node: saves under ``<output_dir>/temp`` (the host's
    temp-image convention) via TPUSaveImage — headless, a preview IS a file
    the client fetches through /view."""

    DESCRIPTION = "Stock-name image preview (saves to the temp subfolder)."
    RETURN_TYPES = ("STRING",)
    RETURN_NAMES = ("paths",)
    FUNCTION = "preview"
    CATEGORY = CATEGORY
    OUTPUT_NODE = True

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"images": ("IMAGE", {})}}

    def preview(self, images):
        from .nodes import TPUSaveImage

        # temp/ subfolder under the served output root: /view can fetch it
        # (subfolder=temp) and the history's relpath logic tags it correctly.
        return TPUSaveImage().save(images, filename_prefix="temp/preview")


class CLIPTextEncodeSDXLRefiner:
    """Stock refiner encode: ONE prompt through the OpenCLIP-G tower with the
    refiner's (size, crop, aesthetic-score) conditioning vector. Accepts the
    sdxl-dual wire (uses its G tower — the stock base→refiner template wires
    the base checkpoint's CLIP here too) or a single G-tower CLIP wire."""

    DESCRIPTION = "Stock-name SDXL-refiner text encode (aesthetic score adm)."
    RETURN_TYPES = ("CONDITIONING",)
    RETURN_NAMES = ("conditioning",)
    FUNCTION = "encode"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "clip": ("CLIP", {}),
                "ascore": ("FLOAT", {"default": 6.0, "min": 0.0,
                                     "max": 1000.0}),
                "width": ("INT", {"default": 1024, "min": 0, "max": 16384}),
                "height": ("INT", {"default": 1024, "min": 0, "max": 16384}),
                "text": ("STRING", {"default": "", "multiline": True}),
            }
        }

    def encode(self, clip, ascore: float, width: int, height: int, text: str):
        from .models.text_encoders import sdxl_refiner_text_conditioning
        from .nodes import TPUTextEncode

        g_wire = clip["g"] if clip.get("type") == "sdxl-dual" else clip
        if g_wire.get("encoder") is None:
            raise ValueError(
                "CLIPTextEncodeSDXLRefiner needs a G-tower CLIP wire (the "
                "sdxl-dual wire from an SDXL checkpoint, or TPUCLIPLoader "
                "type=open-clip-g)"
            )
        clip_skip = int(clip.get("clip_skip", g_wire.get("clip_skip", 0)))
        (cg,) = TPUTextEncode().encode(g_wire, text, clip_skip)
        stream = cg["penultimate"] if clip_skip == 0 else cg["context"]
        context, y = sdxl_refiner_text_conditioning(
            stream, cg["pooled"], width=width, height=height,
            ascore=float(ascore),
        )
        return ({"context": context, "penultimate": None, "pooled": y},)


class ConditioningConcat:
    """Stock concat: ``conditioning_from``'s tokens append onto
    ``conditioning_to``'s along the sequence axis (ONE longer prompt — unlike
    Combine, which keeps both prompts separate and blends predictions).
    conditioning_to's other fields (pooled, control tags, …) win."""

    DESCRIPTION = "Stock-name conditioning token concat."
    RETURN_TYPES = ("CONDITIONING",)
    RETURN_NAMES = ("conditioning",)
    FUNCTION = "concat"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "conditioning_to": ("CONDITIONING", {}),
                "conditioning_from": ("CONDITIONING", {}),
            }
        }

    def concat(self, conditioning_to, conditioning_from):
        import jax.numpy as jnp

        to_ctx = conditioning_to.get("context")
        from_ctx = conditioning_from.get("context")
        if to_ctx is None or from_ctx is None:
            raise ValueError("ConditioningConcat needs text conditionings "
                             "with a context stream on both inputs")
        if to_ctx.shape[-1] != from_ctx.shape[-1]:
            raise ValueError(
                f"cannot concat conditionings of different widths "
                f"({to_ctx.shape[-1]} vs {from_ctx.shape[-1]} — e.g. an SDXL "
                "dual-tower cond with a plain CLIP-L one)"
            )
        if from_ctx.shape[0] != to_ctx.shape[0]:
            from_ctx = _repeat_to_batch(from_ctx, to_ctx.shape[0])
        return ({**conditioning_to,
                 "context": jnp.concatenate([to_ctx, from_ctx], axis=1)},)


class ImageInvert:
    DESCRIPTION = "Stock-name image invert (1 - pixels)."
    RETURN_TYPES = ("IMAGE",)
    RETURN_NAMES = ("image",)
    FUNCTION = "invert"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"image": ("IMAGE", {})}}

    def invert(self, image):
        import jax.numpy as jnp

        return (1.0 - jnp.asarray(image),)


class ImageBatch:
    """Stock batch join: the second image resizes (bilinear) to the first's
    spatial size when they differ, then both concatenate along batch."""

    DESCRIPTION = "Stock-name image batch concat."
    RETURN_TYPES = ("IMAGE",)
    RETURN_NAMES = ("image",)
    FUNCTION = "batch"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"image1": ("IMAGE", {}),
                             "image2": ("IMAGE", {})}}

    def batch(self, image1, image2):
        import jax
        import jax.numpy as jnp

        a = jnp.asarray(image1)
        b = jnp.asarray(image2)
        if a.ndim == 3:
            a = a[None]
        if b.ndim == 3:
            b = b[None]
        if b.shape[1:3] != a.shape[1:3]:
            b = jax.image.resize(
                b, (b.shape[0], *a.shape[1:3], b.shape[-1]), method="bilinear"
            )
        return (jnp.concatenate([a, b], axis=0),)


class RepeatLatentBatch:
    DESCRIPTION = "Stock-name latent batch repeat."
    RETURN_TYPES = ("LATENT",)
    RETURN_NAMES = ("latent",)
    FUNCTION = "repeat"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"samples": ("LATENT", {}),
                             "amount": ("INT", {"default": 1, "min": 1,
                                                "max": 64})}}

    def repeat(self, samples, amount: int):
        import jax.numpy as jnp

        lat = jnp.asarray(samples["samples"])
        out = dict(samples)
        out["samples"] = jnp.tile(
            lat, (int(amount),) + (1,) * (lat.ndim - 1)
        )
        if samples.get("noise_mask") is not None:
            # Cycle the mask up to the SAMPLES batch first (stock
            # repeat_to_batch_size), then tile — so masks stay paired with
            # their samples instead of landing at a batch that matches
            # neither the latents nor 1.
            m = _repeat_to_batch(
                jnp.asarray(samples["noise_mask"]), lat.shape[0]
            )
            out["noise_mask"] = jnp.tile(
                m, (int(amount),) + (1,) * (m.ndim - 1)
            )
        return (out,)


class LatentFromBatch:
    DESCRIPTION = "Stock-name latent batch slice."
    RETURN_TYPES = ("LATENT",)
    RETURN_NAMES = ("latent",)
    FUNCTION = "frombatch"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"samples": ("LATENT", {}),
                             "batch_index": ("INT", {"default": 0, "min": 0,
                                                     "max": 4095}),
                             "length": ("INT", {"default": 1, "min": 1,
                                                "max": 4096})}}

    def frombatch(self, samples, batch_index: int, length: int):
        import jax.numpy as jnp

        lat = jnp.asarray(samples["samples"])
        i = min(int(batch_index), lat.shape[0] - 1)
        n = min(int(length), lat.shape[0] - i)
        out = dict(samples)
        out["samples"] = lat[i:i + n]
        if samples.get("noise_mask") is not None:
            m = jnp.asarray(samples["noise_mask"])
            if m.shape[0] > 1:
                # Cycle up to the samples batch BEFORE slicing (stock rule) —
                # a mask batch smaller than the latent batch would otherwise
                # slice short or empty.
                out["noise_mask"] = _repeat_to_batch(m, lat.shape[0])[i:i + n]
        return (out,)


def _latent_spatial_map(samples_dict, fn):
    """Apply ``fn`` (a spatial-axes transform over channels-last arrays) to
    the latent samples AND its noise_mask — both share rank and the
    (..., H, W, C) layout, so the −3/−2 spatial axes line up for image (NHWC)
    and video (NTHWC) latents alike."""
    import jax.numpy as jnp

    out = dict(samples_dict)
    out["samples"] = fn(jnp.asarray(samples_dict["samples"]))
    if samples_dict.get("noise_mask") is not None:
        out["noise_mask"] = fn(jnp.asarray(samples_dict["noise_mask"]))
    return out


class LatentFlip:
    """Stock latent flip: the menu strings name the axis being mirrored
    ACROSS — "x-axis: vertically" mirrors rows (H), "y-axis: horizontally"
    mirrors columns (W). The attached noise_mask flips with the samples."""

    DESCRIPTION = "Stock-name latent flip (vertical/horizontal)."
    RETURN_TYPES = ("LATENT",)
    RETURN_NAMES = ("latent",)
    FUNCTION = "flip"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "samples": ("LATENT", {}),
            "flip_method": (["x-axis: vertically", "y-axis: horizontally"],
                            {"default": "x-axis: vertically"}),
        }}

    def flip(self, samples, flip_method: str):
        import jax.numpy as jnp

        axis = -3 if flip_method.startswith("x") else -2
        return (_latent_spatial_map(samples, lambda a: jnp.flip(a, axis)),)


class LatentRotate:
    """Stock latent rotate: clockwise quarter-turns over the spatial plane
    (channels-last: H=−3, W=−2; ``jnp.rot90`` with negative k is clockwise).
    The attached noise_mask rotates with the samples."""

    DESCRIPTION = "Stock-name latent rotation (90° steps, clockwise)."
    RETURN_TYPES = ("LATENT",)
    RETURN_NAMES = ("latent",)
    FUNCTION = "rotate"
    CATEGORY = CATEGORY

    _TURNS = {"none": 0, "90 degrees": 1, "180 degrees": 2, "270 degrees": 3}

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "samples": ("LATENT", {}),
            "rotation": (list(cls._TURNS), {"default": "none"}),
        }}

    def rotate(self, samples, rotation: str):
        import jax.numpy as jnp

        k = self._TURNS.get(rotation)
        if k is None:
            raise ValueError(
                f"rotation {rotation!r} is not one of {list(self._TURNS)}"
            )
        if k == 0:
            return (samples,)
        return (_latent_spatial_map(
            samples, lambda a: jnp.rot90(a, k=-k, axes=(-3, -2))
        ),)


class LatentCrop:
    """Stock latent crop: pixel-space (width, height, x, y) → an 8×-downsampled
    latent window with stock's exact boundary rule: the origin clamps to
    (dim − 8) in latent units and the slice then truncates at the latent's
    edge — an oversized or out-of-range window therefore yields a
    smaller-than-requested latent, exactly as the stock node does (it never
    slides the window back to preserve the requested size)."""

    DESCRIPTION = "Stock-name latent crop (pixel coords, /8 latent grid)."
    RETURN_TYPES = ("LATENT",)
    RETURN_NAMES = ("latent",)
    FUNCTION = "crop"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "samples": ("LATENT", {}),
            "width": ("INT", {"default": 512, "min": 64, "max": 16384,
                              "step": 8}),
            "height": ("INT", {"default": 512, "min": 64, "max": 16384,
                               "step": 8}),
            "x": ("INT", {"default": 0, "min": 0, "max": 16384, "step": 8}),
            "y": ("INT", {"default": 0, "min": 0, "max": 16384, "step": 8}),
        }}

    def crop(self, samples, width: int, height: int, x: int, y: int):
        lat = samples["samples"]
        H, W = lat.shape[-3], lat.shape[-2]
        # Stock boundary rule: clamp the origin to (dim − 8) latent units,
        # then let the slice truncate (smaller-than-requested output near the
        # edge). The extra max(…, 0) keeps sub-64px latents slicing from 0
        # instead of a negative index.
        y0 = min(int(y) // 8, max(H - 8, 0))
        x0 = min(int(x) // 8, max(W - 8, 0))
        h = max(1, int(height) // 8)
        w = max(1, int(width) // 8)

        def window(a):
            return a[..., y0:y0 + h, x0:x0 + w, :]

        return (_latent_spatial_map(samples, window),)


class SaveLatent:
    """Stock latent save: a safetensors file holding ``latent_tensor`` plus
    the ``latent_format_version_0`` marker (stock's un-scaled format signal;
    LoadLatent applies the legacy 1/0.18215 rescale only when it is absent).
    The file stores the public stock layout — channels-first NCHW (NCTHW for
    video latents) — so dumps interchange with the stock host; this
    framework's channels-last axes transpose at the file boundary, the same
    contract the checkpoint converters keep for single-file layouts. Saved
    under $PA_OUTPUT_DIR via the same counter/prefix rules as SaveImage."""

    DESCRIPTION = "Stock-name latent save (safetensors)."
    RETURN_TYPES = ()
    FUNCTION = "save"
    CATEGORY = CATEGORY
    OUTPUT_NODE = True

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "samples": ("LATENT", {}),
            "filename_prefix": ("STRING", {"default": "latents/ComfyUI"}),
        }}

    def save(self, samples, filename_prefix: str = "latents/ComfyUI"):
        import numpy as _np
        from safetensors.numpy import save_file

        from .nodes import resolve_save_target

        target_dir, name, idx = resolve_save_target(
            filename_prefix, suffix="latent"
        )
        path = os.path.join(target_dir, f"{name}_{idx:05}.latent")
        # Channels-last (..., H, W, C) → the stock file's channels-first
        # (..., C, H, W): axis -1 moves to position 1 for any latent rank
        # (NHWC image and NTHWC video alike).
        # Contiguous copy: safetensors writes the raw buffer, so a moveaxis
        # view would store NHWC bytes under the NCHW shape.
        arr = _np.ascontiguousarray(_np.moveaxis(
            _np.asarray(samples["samples"], dtype=_np.float32), -1, 1
        ))
        save_file(
            {
                "latent_tensor": arr,
                "latent_format_version_0": _np.zeros((0,), _np.float32),
            },
            path,
        )
        return {"ui": {"latents": [os.path.basename(path)]}}


class LoadLatent:
    """Stock latent load: reads a SaveLatent file from $PA_INPUT_DIR. The
    file holds the stock channels-first layout (NCHW/NCTHW) — axis 1 moves
    back to -1 on read, the inverse of SaveLatent's boundary transpose.
    Files without the ``latent_format_version_0`` marker are stock's legacy
    dumps, stored pre-scaled — multiply by 1/0.18215 to recover latent
    space."""

    DESCRIPTION = "Stock-name latent load (safetensors)."
    RETURN_TYPES = ("LATENT",)
    RETURN_NAMES = ("latent",)
    FUNCTION = "load"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"latent": ("STRING", {"default": ""})}}

    def load(self, latent: str):
        import jax.numpy as jnp
        from safetensors.numpy import load_file

        path = latent
        if not os.path.isabs(path):
            path = os.path.join(os.environ.get("PA_INPUT_DIR", "."), path)
        if not os.path.isfile(path):
            raise ValueError(f"latent file not found: {path}")
        sd = load_file(path)
        if "latent_tensor" not in sd:
            raise ValueError(
                f"{path} is not a saved latent (no latent_tensor key)"
            )
        # Stock channels-first file → this framework's channels-last latents;
        # the legacy 1/0.18215 dumps are stored in the same NCHW layout.
        arr = jnp.moveaxis(jnp.asarray(sd["latent_tensor"], jnp.float32), 1, -1)
        if "latent_format_version_0" not in sd:
            arr = arr * (1.0 / 0.18215)
        return ({"samples": arr},)


class SolidMask:
    DESCRIPTION = "Stock-name constant mask."
    RETURN_TYPES = ("MASK",)
    RETURN_NAMES = ("mask",)
    FUNCTION = "solid"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "value": ("FLOAT", {"default": 1.0, "min": 0.0, "max": 1.0}),
            "width": ("INT", {"default": 512, "min": 1, "max": 16384}),
            "height": ("INT", {"default": 512, "min": 1, "max": 16384}),
        }}

    def solid(self, value: float, width: int, height: int):
        import jax.numpy as jnp

        return (jnp.full((1, int(height), int(width)), float(value),
                         jnp.float32),)


class InvertMask:
    DESCRIPTION = "Stock-name mask invert."
    RETURN_TYPES = ("MASK",)
    RETURN_NAMES = ("mask",)
    FUNCTION = "invert"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"mask": ("MASK", {})}}

    def invert(self, mask):
        import jax.numpy as jnp

        return (1.0 - jnp.asarray(mask, jnp.float32),)


class ImageToMask:
    DESCRIPTION = "Stock-name channel extract (image → mask)."
    RETURN_TYPES = ("MASK",)
    RETURN_NAMES = ("mask",)
    FUNCTION = "image_to_mask"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"image": ("IMAGE", {}),
                             "channel": (["red", "green", "blue", "alpha"],
                                         {"default": "red"})}}

    def image_to_mask(self, image, channel: str = "red"):
        import jax.numpy as jnp

        img = jnp.asarray(image)
        if img.ndim == 3:
            img = img[None]
        idx = {"red": 0, "green": 1, "blue": 2, "alpha": 3}[channel]
        if idx >= img.shape[-1]:
            # Stock indexes an existing channel; a 3-channel image has no
            # alpha — fully-opaque is the faithful reading.
            return (jnp.ones(img.shape[:3], jnp.float32),)
        return (img[..., idx].astype(jnp.float32),)


class MaskToImage:
    DESCRIPTION = "Stock-name mask → grayscale image."
    RETURN_TYPES = ("IMAGE",)
    RETURN_NAMES = ("image",)
    FUNCTION = "mask_to_image"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"mask": ("MASK", {})}}

    def mask_to_image(self, mask):
        import jax.numpy as jnp

        m = jnp.asarray(mask, jnp.float32)
        if m.ndim == 2:
            m = m[None]
        if m.ndim == 4:
            m = m[..., 0]
        return (jnp.repeat(m[..., None], 3, axis=-1),)


class GrowMask:
    """Stock grow/shrink: |expand| iterations of a 3×3 max (grow) or min
    (shrink) window; ``tapered_corners`` excludes the diagonal neighbors
    (the stock plus-shaped kernel), rounding grown corners."""

    DESCRIPTION = "Stock-name mask dilate/erode."
    RETURN_TYPES = ("MASK",)
    RETURN_NAMES = ("mask",)
    FUNCTION = "expand_mask"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "mask": ("MASK", {}),
            "expand": ("INT", {"default": 0, "min": -16384, "max": 16384}),
            "tapered_corners": ("BOOLEAN", {"default": True}),
        }}

    def expand_mask(self, mask, expand: int, tapered_corners: bool = True):
        import jax.numpy as jnp

        m = jnp.asarray(mask, jnp.float32)
        if m.ndim == 2:
            m = m[None]
        grow = expand > 0
        n = min(abs(int(expand)), max(m.shape[1], m.shape[2]))
        for _ in range(n):
            # One 3×3 max/min step; the plus kernel = max over the 4-neighbor
            # shifts + center (diagonals excluded when tapered).
            shifts = [m]
            padded = jnp.pad(
                m, ((0, 0), (1, 1), (1, 1)),
                constant_values=0.0 if grow else 1.0,
            )
            offs = [(-1, 0), (1, 0), (0, -1), (0, 1)]
            if not tapered_corners:
                offs += [(-1, -1), (-1, 1), (1, -1), (1, 1)]
            for dy, dx in offs:
                shifts.append(
                    padded[:, 1 + dy:1 + dy + m.shape[1],
                           1 + dx:1 + dx + m.shape[2]]
                )
            m = (jnp.max(jnp.stack(shifts), axis=0) if grow
                 else jnp.min(jnp.stack(shifts), axis=0))
        return (m,)


class FeatherMask:
    """Stock feather: linear ramp to 0 over the given pixel depth from each
    selected edge."""

    DESCRIPTION = "Stock-name mask edge feather."
    RETURN_TYPES = ("MASK",)
    RETURN_NAMES = ("mask",)
    FUNCTION = "feather"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "mask": ("MASK", {}),
            "left": ("INT", {"default": 0, "min": 0, "max": 16384}),
            "top": ("INT", {"default": 0, "min": 0, "max": 16384}),
            "right": ("INT", {"default": 0, "min": 0, "max": 16384}),
            "bottom": ("INT", {"default": 0, "min": 0, "max": 16384}),
        }}

    def feather(self, mask, left: int, top: int, right: int, bottom: int):
        import jax.numpy as jnp

        m = jnp.asarray(mask, jnp.float32)
        if m.ndim == 2:
            m = m[None]
        _, H, W = m.shape
        rows = jnp.arange(H, dtype=jnp.float32)
        cols = jnp.arange(W, dtype=jnp.float32)
        scale = jnp.ones((H, W), jnp.float32)
        if top:
            scale = scale * jnp.clip((rows[:, None] + 1) / top, 0, 1)
        if bottom:
            scale = scale * jnp.clip((H - rows[:, None]) / bottom, 0, 1)
        if left:
            scale = scale * jnp.clip((cols[None, :] + 1) / left, 0, 1)
        if right:
            scale = scale * jnp.clip((W - cols[None, :]) / right, 0, 1)
        return (m * scale[None],)


class MaskComposite:
    """Stock mask composite: ``source`` pastes onto ``destination`` at
    (x, y) under the selected op (multiply/add/subtract/and/or/xor)."""

    DESCRIPTION = "Stock-name mask composite."
    RETURN_TYPES = ("MASK",)
    RETURN_NAMES = ("mask",)
    FUNCTION = "combine"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "destination": ("MASK", {}),
            "source": ("MASK", {}),
            "x": ("INT", {"default": 0, "min": 0, "max": 16384}),
            "y": ("INT", {"default": 0, "min": 0, "max": 16384}),
            "operation": (["multiply", "add", "subtract", "and", "or", "xor"],
                          {"default": "multiply"}),
        }}

    def combine(self, destination, source, x: int, y: int,
                operation: str = "multiply"):
        import jax.numpy as jnp

        dst = jnp.asarray(destination, jnp.float32)
        src = jnp.asarray(source, jnp.float32)
        if dst.ndim == 2:
            dst = dst[None]
        if src.ndim == 2:
            src = src[None]
        _, H, W = dst.shape
        h = min(src.shape[1], H - min(int(y), H))
        w = min(src.shape[2], W - min(int(x), W))
        if h <= 0 or w <= 0:
            return (dst,)
        src = _repeat_to_batch(src, dst.shape[0])[:, :h, :w]
        win = dst[:, y:y + h, x:x + w]
        ops = {
            "multiply": win * src,
            "add": win + src,
            "subtract": win - src,
            "and": jnp.round(win) * jnp.round(src),
            "or": jnp.clip(jnp.round(win) + jnp.round(src), 0, 1),
            "xor": jnp.abs(jnp.round(win) - jnp.round(src)),
        }
        out = jnp.clip(ops[operation], 0.0, 1.0)
        return (dst.at[:, y:y + h, x:x + w].set(out),)


class LoadImageMask:
    """Stock mask load: one channel of an input-directory image as a MASK
    (alpha inverts, matching stock's 1-alpha regenerate convention)."""

    DESCRIPTION = "Stock-name image-channel mask loader."
    RETURN_TYPES = ("MASK",)
    RETURN_NAMES = ("mask",)
    FUNCTION = "load_image"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"image": ("STRING", {"default": ""}),
                             "channel": (["alpha", "red", "green", "blue"],
                                         {"default": "alpha"})}}

    def load_image(self, image: str, channel: str = "alpha"):
        import jax.numpy as jnp
        import numpy as np

        px, alpha = LoadImage().run(image)
        if channel == "alpha":
            # LoadImage's MASK output is already stock's 1-alpha.
            return (jnp.asarray(alpha),)
        arr = np.asarray(px)
        idx = {"red": 0, "green": 1, "blue": 2}[channel]
        return (jnp.asarray(arr[..., idx], jnp.float32),)


class CLIPTextEncodeFlux:
    """Stock FLUX encode: SEPARATE prompts per tower (clip_l → pooled,
    t5xxl → context stream) + the distilled-guidance tag in one node — the
    stock FLUX template's text entry."""

    DESCRIPTION = "Stock-name FLUX dual-prompt encode with guidance tag."
    RETURN_TYPES = ("CONDITIONING",)
    RETURN_NAMES = ("conditioning",)
    FUNCTION = "encode"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "clip": ("CLIP", {}),
            "clip_l": ("STRING", {"default": "", "multiline": True}),
            "t5xxl": ("STRING", {"default": "", "multiline": True}),
            "guidance": ("FLOAT", {"default": 3.5, "min": 0.0,
                                   "max": 100.0}),
        }}

    def encode(self, clip, clip_l: str, t5xxl: str, guidance: float = 3.5):
        from .nodes import TPUFluxGuidance, TPUTextEncode

        if clip.get("type") != "flux-dual":
            raise ValueError(
                "CLIPTextEncodeFlux needs the dual T5+CLIP-L wire "
                "(DualCLIPLoader type=flux)"
            )
        # Honor a CLIPSetLastLayer tag on the dual wire (it lands on the
        # OUTER dict) — same convention as CLIPTextEncodeSDXL.
        clip_skip = int(clip.get("clip_skip", 0))
        enc = TPUTextEncode()
        (ct5,) = enc.encode(clip["t5"], t5xxl, clip_skip)
        (cl,) = enc.encode(clip["l"], clip_l, clip_skip)
        cond = {"context": ct5["context"], "penultimate": None,
                "pooled": cl["pooled"]}
        (tagged,) = TPUFluxGuidance().append(cond, float(guidance))
        return (tagged,)


class ConditioningSetAreaPercentage:
    """Stock percentage form of SetArea: the box is fractions of the LATENT
    frame, resolved per-sample at denoise time — here resolved against the
    stock 8× latent convention like the pixel form."""

    DESCRIPTION = "Stock-name fractional area conditioning."
    RETURN_TYPES = ("CONDITIONING",)
    RETURN_NAMES = ("conditioning",)
    FUNCTION = "append"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "conditioning": ("CONDITIONING", {}),
            "width": ("FLOAT", {"default": 1.0, "min": 0.0, "max": 1.0,
                                "step": 0.01}),
            "height": ("FLOAT", {"default": 1.0, "min": 0.0, "max": 1.0,
                                 "step": 0.01}),
            "x": ("FLOAT", {"default": 0.0, "min": 0.0, "max": 1.0,
                            "step": 0.01}),
            "y": ("FLOAT", {"default": 0.0, "min": 0.0, "max": 1.0,
                            "step": 0.01}),
            "strength": ("FLOAT", {"default": 1.0, "min": 0.0, "max": 10.0}),
        }}

    def append(self, conditioning, width: float, height: float, x: float,
               y: float, strength: float = 1.0):
        # Stock stores BOTH forms under one "area" key, so the later node
        # always wins; here the forms are separate keys — clear the sibling.
        return (_tag_all_entries(conditioning, {
            "area_pct": (float(height), float(width), float(y), float(x)),
            "area": None,
            "strength": float(strength),
        }),)


class ImageScaleToTotalPixels:
    """Stock megapixel-normalize (the FLUX template's input-size step):
    resize to ``megapixels`` total, aspect preserved."""

    DESCRIPTION = "Stock-name scale-to-megapixels."
    RETURN_TYPES = ("IMAGE",)
    RETURN_NAMES = ("image",)
    FUNCTION = "upscale"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "image": ("IMAGE", {}),
            "upscale_method": (list(_STOCK_RESIZE), {"default": "bilinear"}),
            "megapixels": ("FLOAT", {"default": 1.0, "min": 0.01,
                                     "max": 16.0, "step": 0.01}),
        }}

    def upscale(self, image, upscale_method: str, megapixels: float):
        import jax.numpy as jnp

        img = jnp.asarray(image)
        if img.ndim == 3:
            img = img[None]
        _, H, W, _ = img.shape
        scale = (float(megapixels) * 1024 * 1024 / (H * W)) ** 0.5
        nh, nw = max(1, round(H * scale)), max(1, round(W * scale))
        # The shared stock-resize core: method validation + the [0,1] clip
        # (lanczos/bicubic overshoot) the sibling resize nodes apply.
        return (_stock_resize(img, nw, nh, upscale_method),)


class ModelMergeSimple:
    """Stock weighted model merge: ``ratio`` of model1 + ``1−ratio`` of
    model2, leaf-wise over the param pytrees. Both models must share a
    family/topology (identical tree structure — the stock constraint too)."""

    DESCRIPTION = "Stock-name weighted model merge."
    RETURN_TYPES = ("MODEL",)
    RETURN_NAMES = ("model",)
    FUNCTION = "merge"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "model1": ("MODEL", {}),
            "model2": ("MODEL", {}),
            "ratio": ("FLOAT", {"default": 1.0, "min": 0.0, "max": 1.0,
                                "step": 0.01}),
        }}

    def merge(self, model1, model2, ratio: float):
        import dataclasses as dc

        import jax

        if not (dc.is_dataclass(model1) and dc.is_dataclass(model2)):
            raise ValueError(
                "ModelMergeSimple needs unwrapped MODELs; apply it before "
                "ParallelAnything"
            )
        r = float(ratio)

        def lerp(a, b):
            if getattr(a, "shape", None) != getattr(b, "shape", None):
                # Same tree structure but different widths (e.g. two UNets
                # built at different model_channels) must fail loudly, not
                # broadcast into silently corrupted params.
                raise ValueError(f"leaf shapes differ: {a.shape} vs {b.shape}")
            return a * r + b * (1.0 - r)

        from .models.loader import residency

        residency.ensure(model1.params)
        residency.ensure(model2.params)
        try:
            merged = jax.tree.map(lerp, model1.params, model2.params)
        except (ValueError, TypeError) as e:
            raise ValueError(
                "models cannot merge — different families/topologies "
                f"({e})"
            ) from None
        # The merged weights correspond to neither source file, so the
        # re-bake LoRA path has nothing to re-bake from: a marker source
        # makes the downstream LoraLoader error name the real cause.
        return (dc.replace(model1, params=merged, source={"merged": True},
                           name=f"{model1.name}+merge"),)


class ImageCrop:
    DESCRIPTION = "Stock-name image crop."
    RETURN_TYPES = ("IMAGE",)
    RETURN_NAMES = ("image",)
    FUNCTION = "crop"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "image": ("IMAGE", {}),
            "width": ("INT", {"default": 512, "min": 1, "max": 16384}),
            "height": ("INT", {"default": 512, "min": 1, "max": 16384}),
            "x": ("INT", {"default": 0, "min": 0, "max": 16384}),
            "y": ("INT", {"default": 0, "min": 0, "max": 16384}),
        }}

    def crop(self, image, width: int, height: int, x: int, y: int):
        import jax.numpy as jnp

        img = jnp.asarray(image)
        if img.ndim == 3:
            img = img[None]
        B, H, W, C = img.shape
        x = min(int(x), W - 1)
        y = min(int(y), H - 1)
        return (img[:, y:min(y + int(height), H), x:min(x + int(width), W)],)


def _gaussian_kernel1d(radius: int, sigma: float):
    import jax.numpy as jnp

    xs = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
    k = jnp.exp(-(xs**2) / (2.0 * float(sigma) ** 2))
    return k / jnp.sum(k)


def _separable_blur(img, radius: int, sigma: float):
    """Edge-padded separable Gaussian over (B,H,W,C) — the shared primitive
    of the stock blur/sharpen pair."""
    import jax
    import jax.numpy as jnp

    k = _gaussian_kernel1d(radius, sigma)
    pad = int(radius)
    # reflect, not edge: stock's Blur/Sharpen pad reflectively — edge
    # replication over-weights the outermost row and diverges on borders.
    x = jnp.pad(img, ((0, 0), (pad, pad), (pad, pad), (0, 0)),
                mode="reflect")
    # Two depthwise 1-D convolutions (separable Gaussian).
    x = jax.lax.conv_general_dilated(
        x.transpose(0, 3, 1, 2), jnp.broadcast_to(
            k.reshape(1, 1, -1, 1), (img.shape[-1], 1, 2 * pad + 1, 1)),
        (1, 1), "VALID", feature_group_count=img.shape[-1],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )
    x = jax.lax.conv_general_dilated(
        x, jnp.broadcast_to(
            k.reshape(1, 1, 1, -1), (img.shape[-1], 1, 1, 2 * pad + 1)),
        (1, 1), "VALID", feature_group_count=img.shape[-1],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )
    return x.transpose(0, 2, 3, 1)


class ImageBlur:
    DESCRIPTION = "Stock-name Gaussian image blur."
    RETURN_TYPES = ("IMAGE",)
    RETURN_NAMES = ("image",)
    FUNCTION = "blur"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "image": ("IMAGE", {}),
            "blur_radius": ("INT", {"default": 1, "min": 1, "max": 31}),
            "sigma": ("FLOAT", {"default": 1.0, "min": 0.1, "max": 10.0,
                                "step": 0.1}),
        }}

    def blur(self, image, blur_radius: int, sigma: float):
        import jax.numpy as jnp

        img = jnp.asarray(image)
        if img.ndim == 3:
            img = img[None]
        return (_separable_blur(img, int(blur_radius), float(sigma)),)


class ImageSharpen:
    """Stock unsharp mask: img + alpha·(img − gaussian(img)), clipped."""

    DESCRIPTION = "Stock-name image sharpen (unsharp mask)."
    RETURN_TYPES = ("IMAGE",)
    RETURN_NAMES = ("image",)
    FUNCTION = "sharpen"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "image": ("IMAGE", {}),
            "sharpen_radius": ("INT", {"default": 1, "min": 1, "max": 31}),
            "sigma": ("FLOAT", {"default": 1.0, "min": 0.1, "max": 10.0,
                                "step": 0.1}),
            "alpha": ("FLOAT", {"default": 1.0, "min": 0.0, "max": 5.0,
                                "step": 0.1}),
        }}

    def sharpen(self, image, sharpen_radius: int, sigma: float, alpha: float):
        import jax.numpy as jnp

        img = jnp.asarray(image)
        if img.ndim == 3:
            img = img[None]
        blurred = _separable_blur(img, int(sharpen_radius), float(sigma))
        return (jnp.clip(img + float(alpha) * (img - blurred), 0.0, 1.0),)


class LatentBlend:
    DESCRIPTION = "Stock-name latent lerp."
    RETURN_TYPES = ("LATENT",)
    RETURN_NAMES = ("latent",)
    FUNCTION = "blend"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "samples1": ("LATENT", {}),
            "samples2": ("LATENT", {}),
            "blend_factor": ("FLOAT", {"default": 0.5, "min": 0.0,
                                       "max": 1.0, "step": 0.01}),
        }}

    def blend(self, samples1, samples2, blend_factor: float):
        import jax.numpy as jnp

        a = jnp.asarray(samples1["samples"])
        b = _reshape_latent_to(a, jnp.asarray(samples2["samples"]))
        f = float(blend_factor)
        # Stock LatentBlend: samples1·factor + samples2·(1−factor).
        return ({**samples1, "samples": a * f + b * (1.0 - f)},)


def _reshape_latent_to(a, b):
    """Stock reshape_latent_to: resize ``b``'s SPATIAL grid to ``a``'s and
    cycle its batch up — the two-latent math nodes all normalize this way.
    Channel counts must already agree (resizing across channels would
    fabricate latent data; stock fails loudly there too)."""
    import jax
    import jax.numpy as jnp

    if a.shape[-1] != b.shape[-1]:
        raise ValueError(
            f"latent channel counts differ ({a.shape[-1]} vs {b.shape[-1]} — "
            "e.g. an SD1.5 latent mixed with an SD3/FLUX one); latent math "
            "needs same-family latents"
        )
    if a.shape[1:-1] != b.shape[1:-1]:
        b = jax.image.resize(
            b, (b.shape[0], *a.shape[1:-1], b.shape[-1]), method="bilinear"
        )
    return _repeat_to_batch(b, a.shape[0])


def _latent_binop(stock_name: str, fn):
    class _Op:
        DESCRIPTION = f"Stock-name latent op {stock_name}."
        RETURN_TYPES = ("LATENT",)
        RETURN_NAMES = ("latent",)
        FUNCTION = "op"
        CATEGORY = CATEGORY

        @classmethod
        def INPUT_TYPES(cls):
            return {"required": {"samples1": ("LATENT", {}),
                                 "samples2": ("LATENT", {})}}

        def op(self, samples1, samples2):
            import jax.numpy as jnp

            a = jnp.asarray(samples1["samples"])
            b = _reshape_latent_to(a, jnp.asarray(samples2["samples"]))
            return ({**samples1, "samples": fn(a, b)},)

    _Op.__name__ = stock_name
    return _Op


class LatentInterpolate:
    """Stock norm-preserving latent interpolation: directions lerp after
    per-pixel channel-norm normalization, magnitudes lerp separately, then
    recombine (nodes_latent.py LatentInterpolate)."""

    DESCRIPTION = "Stock-name norm-preserving latent interpolate."
    RETURN_TYPES = ("LATENT",)
    RETURN_NAMES = ("latent",)
    FUNCTION = "op"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "samples1": ("LATENT", {}),
            "samples2": ("LATENT", {}),
            "ratio": ("FLOAT", {"default": 1.0, "min": 0.0, "max": 1.0,
                                "step": 0.01}),
        }}

    def op(self, samples1, samples2, ratio: float):
        import jax.numpy as jnp

        a = jnp.asarray(samples1["samples"])
        b = _reshape_latent_to(a, jnp.asarray(samples2["samples"]))
        r = float(ratio)
        # Channel-axis norms (torch dim=1 on NCHW == our last axis).
        na = jnp.linalg.norm(a, axis=-1, keepdims=True)
        nb = jnp.linalg.norm(b, axis=-1, keepdims=True)
        da = jnp.where(na > 0, a / jnp.maximum(na, 1e-12), 0.0)
        db = jnp.where(nb > 0, b / jnp.maximum(nb, 1e-12), 0.0)
        t = da * r + db * (1.0 - r)
        nt = jnp.linalg.norm(t, axis=-1, keepdims=True)
        st = jnp.where(nt > 0, t / jnp.maximum(nt, 1e-12), 0.0)
        return ({**samples1,
                 "samples": st * (na * r + nb * (1.0 - r))},)


class LatentMultiply:
    """Stock scalar latent multiply (samples × multiplier) — unlike
    Add/Subtract this one takes a FLOAT, not a second latent."""

    DESCRIPTION = "Stock-name latent scalar multiply."
    RETURN_TYPES = ("LATENT",)
    RETURN_NAMES = ("latent",)
    FUNCTION = "op"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "samples": ("LATENT", {}),
            "multiplier": ("FLOAT", {"default": 1.0, "min": -10.0,
                                     "max": 10.0, "step": 0.01}),
        }}

    def op(self, samples, multiplier: float):
        import jax.numpy as jnp

        return ({**samples,
                 "samples": jnp.asarray(samples["samples"])
                 * float(multiplier)},)


class LatentBatch:
    """Stock latent batch join (resizes the second to the first's grid like
    ImageBatch)."""

    DESCRIPTION = "Stock-name latent batch concat."
    RETURN_TYPES = ("LATENT",)
    RETURN_NAMES = ("latent",)
    FUNCTION = "batch"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"samples1": ("LATENT", {}),
                             "samples2": ("LATENT", {})}}

    def batch(self, samples1, samples2):
        import jax
        import jax.numpy as jnp

        a = jnp.asarray(samples1["samples"])
        b = jnp.asarray(samples2["samples"])
        if a.shape[1:-1] != b.shape[1:-1]:
            b = jax.image.resize(
                b, (b.shape[0], *a.shape[1:-1], b.shape[-1]),
                method="bilinear",
            )
        return ({**samples1, "samples": jnp.concatenate([a, b], axis=0)},)


class KarrasScheduler:
    """Stock custom-sampling Karras sigma node → SIGMAS wire
    (sampling/k_samplers.karras_sigmas)."""

    DESCRIPTION = "Stock-name Karras sigma schedule."
    RETURN_TYPES = ("SIGMAS",)
    RETURN_NAMES = ("sigmas",)
    FUNCTION = "get_sigmas"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "steps": ("INT", {"default": 20, "min": 1, "max": 10000}),
            "sigma_max": ("FLOAT", {"default": 14.614642, "min": 0.0,
                                    "max": 5000.0, "step": 0.01}),
            "sigma_min": ("FLOAT", {"default": 0.0291675, "min": 0.0,
                                    "max": 5000.0, "step": 0.01}),
            "rho": ("FLOAT", {"default": 7.0, "min": 0.0, "max": 100.0,
                              "step": 0.01}),
        }}

    def get_sigmas(self, steps: int, sigma_max: float, sigma_min: float,
                   rho: float):
        from .sampling.k_samplers import karras_sigmas

        return (karras_sigmas(int(steps), sigma_min=float(sigma_min),
                              sigma_max=float(sigma_max), rho=float(rho)),)


class ExponentialScheduler:
    DESCRIPTION = "Stock-name exponential (log-uniform) sigma schedule."
    RETURN_TYPES = ("SIGMAS",)
    RETURN_NAMES = ("sigmas",)
    FUNCTION = "get_sigmas"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "steps": ("INT", {"default": 20, "min": 1, "max": 10000}),
            "sigma_max": ("FLOAT", {"default": 14.614642, "min": 0.0,
                                    "max": 5000.0, "step": 0.01}),
            "sigma_min": ("FLOAT", {"default": 0.0291675, "min": 0.0,
                                    "max": 5000.0, "step": 0.01}),
        }}

    def get_sigmas(self, steps: int, sigma_max: float, sigma_min: float):
        from .sampling.k_samplers import exponential_sigmas

        return (exponential_sigmas(int(steps), sigma_min=float(sigma_min),
                                   sigma_max=float(sigma_max)),)


class SDTurboScheduler:
    """Stock SD-Turbo schedule: the model's top ``steps`` trained sigmas
    offset by denoise (turbo models sample in 1-4 steps from raw table
    entries, not interpolated spacings)."""

    DESCRIPTION = "Stock-name SD-Turbo sigma schedule."
    RETURN_TYPES = ("SIGMAS",)
    RETURN_NAMES = ("sigmas",)
    FUNCTION = "get_sigmas"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "model": ("MODEL", {}),
            "steps": ("INT", {"default": 1, "min": 1, "max": 10}),
            "denoise": ("FLOAT", {"default": 1.0, "min": 0.0, "max": 1.0,
                                  "step": 0.01}),
        }}

    def get_sigmas(self, model, steps: int, denoise: float = 1.0):
        import jax.numpy as jnp

        from .sampling.k_samplers import model_sigmas
        from .sampling.schedules import scaled_linear_schedule

        pred = getattr(getattr(model, "config", None), "prediction", "eps")
        if pred == "flow":
            raise ValueError(
                "SDTurboScheduler reads the SD eps/v trained-sigma ladder — "
                "flow-family models schedule with BasicScheduler instead"
            )
        # Stock: a fixed 10-rung ladder of trained timesteps [999, 899, …,
        # 99], sliced [start : start+steps] with start = 10 − int(10·denoise)
        # — slicing TRUNCATES past the end (no clamping: a repeated sigma
        # would divide-by-zero the multistep samplers).
        table = model_sigmas(scaled_linear_schedule())
        ladder = [i * 100 - 1 for i in range(10, 0, -1)]
        start = 10 - int(10 * float(denoise))
        idx = ladder[start:start + int(steps)]
        if not idx:
            raise ValueError(
                f"denoise {denoise} leaves no turbo steps (start rung "
                f"{start} of 10)"
            )
        sig = table[jnp.asarray(idx, jnp.int32)]
        return (jnp.concatenate([sig, jnp.zeros((1,), jnp.float32)]),)


def _named_sampler(stock_name: str, sampler_name: str):
    """A stock named-sampler node (SamplerEulerAncestral, …) → SAMPLER wire.
    Stock variants carry eta/noise widgets; the TPU samplers run their
    k-diffusion defaults, so the wires are name-only (divergence documented
    in the sampler module)."""

    class _Named:
        DESCRIPTION = f"Stock-name SAMPLER wire for {sampler_name}."
        RETURN_TYPES = ("SAMPLER",)
        RETURN_NAMES = ("sampler",)
        FUNCTION = "get_sampler"
        CATEGORY = CATEGORY

        @classmethod
        def INPUT_TYPES(cls):
            return {"required": {}}

        def get_sampler(self, **_ignored):
            return ({"sampler": sampler_name},)

    _Named.__name__ = stock_name
    return _Named


class SamplerCustom:
    """Stock SamplerCustom — the older one-box custom-sampling driver (MODEL
    + conds + SAMPLER + SIGMAS in one node, vs SamplerCustomAdvanced's
    NOISE/GUIDER split). Composes the same wires and delegates."""

    DESCRIPTION = "Stock-name custom-sampling driver (pre-Advanced form)."
    RETURN_TYPES = ("LATENT", "LATENT")
    RETURN_NAMES = ("output", "denoised_output")
    FUNCTION = "sample"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "model": ("MODEL", {}),
            "add_noise": ("BOOLEAN", {"default": True}),
            "noise_seed": ("INT", {"default": 0, "min": 0, "max": 2**64 - 1}),
            "cfg": ("FLOAT", {"default": 8.0, "min": 0.0, "max": 100.0}),
            "positive": ("CONDITIONING", {}),
            "negative": ("CONDITIONING", {}),
            "sampler": ("SAMPLER", {}),
            "sigmas": ("SIGMAS", {}),
            "latent_image": ("LATENT", {}),
        }}

    def sample(self, model, add_noise, noise_seed: int, cfg: float,
               positive, negative, sampler, sigmas, latent_image):
        from .nodes import TPUSamplerCustomAdvanced

        noise = {"seed": int(noise_seed) if add_noise else None}
        guider = {"model": model, "positive": positive,
                  "negative": negative, "cfg": float(cfg)}
        return TPUSamplerCustomAdvanced().sample(
            noise, guider, sampler, sigmas, latent_image
        )


class unCLIPCheckpointLoader:
    """Stock unCLIP loader: the sd21-unclip single file bundles a FOURTH
    component — its ViT-H image encoder (OpenCLIP layout under
    ``embedder.model.visual.*``) — which feeds CLIPVisionEncode →
    unCLIPConditioning. Model/CLIP/VAE load exactly like
    CheckpointLoaderSimple (family sniffed)."""

    DESCRIPTION = "Stock-name unCLIP checkpoint loader (incl. vision tower)."
    RETURN_TYPES = ("MODEL", "CLIP", "VAE", "CLIP_VISION")
    RETURN_NAMES = ("model", "clip", "vae", "clip_vision")
    FUNCTION = "load"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"ckpt_name": ("STRING", {"default": ""})}}

    def load(self, ckpt_name: str):
        from .models.loader import (
            load_safetensors_subset,
            peek_safetensors,
        )
        from .models.vision import build_clip_vision, convert_clip_vision_checkpoint

        pfx = "embedder.model.visual."
        # Header peek BEFORE materializing anything: pointing this node at a
        # plain multi-GB checkpoint must fail in milliseconds, not after the
        # whole model/clip/vae convert.
        path = resolve_model_file(ckpt_name, "checkpoints")
        if not any(k.startswith(pfx) for k in peek_safetensors(path)):
            raise ValueError(
                "checkpoint has no bundled image encoder "
                f"({pfx}*) — not an unCLIP checkpoint; use "
                "CheckpointLoaderSimple + CLIPVisionLoader instead"
            )
        model, clip, vae = CheckpointLoaderSimple().load(ckpt_name)
        tower = load_safetensors_subset(path, pfx)
        params, vcfg = convert_clip_vision_checkpoint(
            {k[len(pfx):]: v for k, v in tower.items()}
        )
        vision = build_clip_vision(vcfg, params=params, name="unclip-vision")
        return model, clip, vae, {"model": vision}


class ModelSamplingDiscrete:
    """Stock prediction-type override: exported workflows fix v-prediction
    checkpoints (weight-indistinguishable from eps — see the sniffing
    warning in models/loader.py) with this node; here it rewrites
    ``config.prediction``, which the samplers read. ``zsnr`` (zero-terminal-
    SNR sigma rescale) is accepted but not applied — logged divergence, the
    sampling still runs."""

    DESCRIPTION = "Stock-name prediction-type (eps/v) model patch."
    RETURN_TYPES = ("MODEL",)
    RETURN_NAMES = ("model",)
    FUNCTION = "patch"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "model": ("MODEL", {}),
            "sampling": (["eps", "v_prediction", "lcm", "x0"],
                         {"default": "eps"}),
            "zsnr": ("BOOLEAN", {"default": False}),
        }}

    def patch(self, model, sampling: str = "eps", zsnr: bool = False):
        import dataclasses as dc

        from .utils.logging import get_logger

        pred = {"eps": "eps", "v_prediction": "v"}.get(sampling)
        if pred is None:
            raise ValueError(
                f"ModelSamplingDiscrete sampling={sampling!r} is not "
                "supported (eps / v_prediction are)"
            )
        if zsnr:
            get_logger().warning(
                "ModelSamplingDiscrete zsnr=True: zero-terminal-SNR sigma "
                "rescale is not applied (documented divergence) — sampling "
                "proceeds with the standard schedule"
            )
        cfg = getattr(model, "config", None)
        if (not dc.is_dataclass(model) or cfg is None
                or not dc.is_dataclass(cfg) or not hasattr(cfg, "prediction")):
            # A ParallelModel's .config is a ParallelConfig (dataclass, no
            # prediction field) — the guard must catch it, not fall through
            # to an opaque dc.replace TypeError.
            raise ValueError(
                "ModelSamplingDiscrete needs an unwrapped MODEL whose config "
                f"carries a prediction field (got {type(model).__name__}); "
                "apply it before ParallelAnything"
            )
        # source/sampler_prefs are DiffusionModel FIELDS, so dc.replace
        # carries them (downstream LoraLoader depends on source).
        return (dc.replace(model, config=dc.replace(cfg, prediction=pred)),)


class EmptyHunyuanLatentVideo:
    """Stock empty VIDEO latent (the t2v entry of WAN/Hunyuan template
    exports): 16-channel, 8x spatial, 4x temporal compression —
    (B, (length-1)//4+1, H/8, W/8, 16) in this repo's NTHWC convention."""

    DESCRIPTION = "Stock-name empty video latent (WAN/Hunyuan t2v)."
    RETURN_TYPES = ("LATENT",)
    RETURN_NAMES = ("latent",)
    FUNCTION = "generate"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "width": ("INT", {"default": 848, "min": 16, "max": 8192,
                              "step": 16}),
            "height": ("INT", {"default": 480, "min": 16, "max": 8192,
                               "step": 16}),
            "length": ("INT", {"default": 25, "min": 1, "max": 1024,
                               "step": 4}),
            "batch_size": ("INT", {"default": 1, "min": 1, "max": 16}),
        }}

    def generate(self, width: int, height: int, length: int,
                 batch_size: int = 1):
        from .nodes import TPUEmptyVideoLatent

        # Stock floors off-schedule lengths (((length-1)//4)+1 latent
        # frames); API submissions bypass widget steps, so accept any length.
        frames = max(1, (int(length) - 1) // 4 * 4 + 1)
        # Delegate: the TPU node derives t_lat/spatial factor AND the
        # default channel count from wan_vae_config (single owner).
        return TPUEmptyVideoLatent().generate(
            width=width, height=height, frames=frames, batch_size=batch_size
        )


class _FreeUBase:
    """Shared FreeU patch machinery: rebuild the UNet module around the SAME
    params with ``cfg.freeu`` set (the patch is an architecture knob here, so
    it survives conversion/parallelize like any other config field). Applies
    to SD-family UNET models, before ParallelAnything — stock ordering."""

    RETURN_TYPES = ("MODEL",)
    RETURN_NAMES = ("model",)
    FUNCTION = "patch"
    CATEGORY = CATEGORY
    _VERSION = 2

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "model": ("MODEL", {}),
            "b1": ("FLOAT", {"default": 1.3 if cls._VERSION >= 2 else 1.1,
                             "min": 0.0, "max": 10.0, "step": 0.01}),
            "b2": ("FLOAT", {"default": 1.4 if cls._VERSION >= 2 else 1.2,
                             "min": 0.0, "max": 10.0, "step": 0.01}),
            "s1": ("FLOAT", {"default": 0.9, "min": 0.0, "max": 10.0,
                             "step": 0.01}),
            "s2": ("FLOAT", {"default": 0.2, "min": 0.0, "max": 10.0,
                             "step": 0.01}),
        }}

    def patch(self, model, b1: float, b2: float, s1: float, s2: float):
        import dataclasses as dc

        from .models import build_unet
        from .models.unet import UNetConfig

        cfg = getattr(model, "config", None)
        if not isinstance(cfg, UNetConfig):
            raise ValueError(
                "FreeU patches SD-family UNET models (config "
                f"{type(cfg).__name__}); apply it between the checkpoint "
                "loader and ParallelAnything/KSampler"
            )
        patched = build_unet(
            dc.replace(cfg, freeu=(float(b1), float(b2), float(s1),
                                   float(s2), self._VERSION)),
            params=model.params, name=f"{model.name}+freeu",
        )
        # build_unet constructs a FRESH DiffusionModel: carry the loader's
        # source tag (LoraLoader re-bakes from it) and any sampler prefs.
        return (dc.replace(patched, sampler_prefs=model.sampler_prefs,
                           source=getattr(model, "source", None)),)


class FreeU(_FreeUBase):
    DESCRIPTION = "Stock-name FreeU model patch (v1: constant backbone scale)."
    _VERSION = 1


class FreeU_V2(_FreeUBase):
    DESCRIPTION = "Stock-name FreeU_V2 model patch (hidden-mean-modulated)."
    _VERSION = 2


class RescaleCFG:
    """Stock RescaleCFG model patch: tags the MODEL with a cfg_rescale
    default the samplers honor (sampling/cfg.rescale_guidance — Lin et al.
    2023). An explicit non-zero cfg_rescale widget on a sampler node wins."""

    DESCRIPTION = "Stock-name CFG-rescale model patch."
    RETURN_TYPES = ("MODEL",)
    RETURN_NAMES = ("model",)
    FUNCTION = "patch"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "model": ("MODEL", {}),
            "multiplier": ("FLOAT", {"default": 0.7, "min": 0.0, "max": 1.0,
                                     "step": 0.01}),
        }}

    def patch(self, model, multiplier: float):
        import copy
        import dataclasses as dc

        prefs = {**(getattr(model, "sampler_prefs", None) or {}),
                 "cfg_rescale": float(multiplier)}
        if dc.is_dataclass(model) and not isinstance(model, type):
            return (dc.replace(model, sampler_prefs=prefs),)
        # ParallelModel and friends: shallow-copy the wrapper (placements are
        # shared; the copy carries no GC finalizer, the original owns
        # teardown) and tag the copy.
        m = copy.copy(model)
        m.sampler_prefs = prefs
        return (m,)


def _patch_sampler_prefs(model, **updates):
    """Merge ``updates`` into the MODEL's sampler_prefs (the RescaleCFG
    carrier): dataclass models get dc.replace, ParallelModel wrappers a
    shallow copy (placements shared; the copy carries no GC finalizer)."""
    import copy
    import dataclasses as dc

    prefs = {**(getattr(model, "sampler_prefs", None) or {}), **updates}
    if dc.is_dataclass(model) and not isinstance(model, type):
        return dc.replace(model, sampler_prefs=prefs)
    m = copy.copy(model)
    m.sampler_prefs = prefs
    return m


class ModelSamplingSD3:
    """Stock SD3 schedule patch: tags the MODEL with the rectified-flow
    timestep shift (default 3.0 — SD3's trained resolution shift). The
    samplers and BasicScheduler read it as their shift default; an explicit
    non-default shift widget wins (same precedence as RescaleCFG's
    cfg_rescale)."""

    DESCRIPTION = "Stock-name SD3 flow-shift model patch."
    RETURN_TYPES = ("MODEL",)
    RETURN_NAMES = ("model",)
    FUNCTION = "patch"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "model": ("MODEL", {}),
            "shift": ("FLOAT", {"default": 3.0, "min": 0.0, "max": 100.0,
                                "step": 0.01}),
        }}

    def patch(self, model, shift: float = 3.0):
        return (_patch_sampler_prefs(model, shift=float(shift)),)


class ModelSamplingAuraFlow(ModelSamplingSD3):
    """Stock AuraFlow schedule patch — the node ComfyUI's Z-Image template
    sets its flow shift with (3.0): the same shift on the same flow table as
    ``ModelSamplingSD3``, under its own stock name."""

    DESCRIPTION = "Stock-name flow-shift patch (AuraFlow / Z-Image templates)."

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "model": ("MODEL", {}),
            "shift": ("FLOAT", {"default": 1.73, "min": 0.0, "max": 100.0,
                                "step": 0.01}),
        }}

    def patch(self, model, shift: float = 1.73):
        return super().patch(model, shift)


class ModelSamplingFlux:
    """Stock FLUX schedule patch: the resolution-dependent flow shift. Stock
    linearly interpolates the LOG-shift (mu) over the latent token count —
    base_shift at 256 tokens to max_shift at 4096 — and warps with
    exp(mu)·t/(1+(exp(mu)−1)·t); at the 1024² defaults the effective shift is
    exp(1.15) ≈ 3.16. The exp(mu) value lands in sampler_prefs as the
    samplers' shift default (explicit non-default widget wins)."""

    DESCRIPTION = "Stock-name FLUX resolution-shift model patch."
    RETURN_TYPES = ("MODEL",)
    RETURN_NAMES = ("model",)
    FUNCTION = "patch"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "model": ("MODEL", {}),
            "max_shift": ("FLOAT", {"default": 1.15, "min": 0.0, "max": 100.0,
                                    "step": 0.01}),
            "base_shift": ("FLOAT", {"default": 0.5, "min": 0.0, "max": 100.0,
                                     "step": 0.01}),
            "width": ("INT", {"default": 1024, "min": 16, "max": 16384}),
            "height": ("INT", {"default": 1024, "min": 16, "max": 16384}),
        }}

    def patch(self, model, max_shift: float = 1.15, base_shift: float = 0.5,
              width: int = 1024, height: int = 1024):
        import math

        # Latent tokens: 8x VAE downsample then 2x2 patchify → (w/16)·(h/16).
        tokens = (width / 16.0) * (height / 16.0)
        m = (max_shift - base_shift) / (4096.0 - 256.0)
        mu = tokens * m + (base_shift - m * 256.0)
        return (_patch_sampler_prefs(model, shift=float(math.exp(mu))),)


class ConditioningSetMask:
    """Stock mask-scoped conditioning: the cond's prediction applies with
    per-pixel weight from a MASK (resized to the latent grid at sampling
    time). ``set_cond_area`` accepted for export parity — "mask bounds" is
    stock's compute-crop optimization and produces the same weights as
    "default" here."""

    DESCRIPTION = "Stock-name mask-scoped conditioning."
    RETURN_TYPES = ("CONDITIONING",)
    RETURN_NAMES = ("conditioning",)
    FUNCTION = "append"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {
            "conditioning": ("CONDITIONING", {}),
            "mask": ("MASK", {}),
            "strength": ("FLOAT", {"default": 1.0, "min": 0.0, "max": 10.0,
                                   "step": 0.01}),
            "set_cond_area": (["default", "mask bounds"],
                              {"default": "default"}),
        }}

    def append(self, conditioning, mask, strength: float = 1.0,
               set_cond_area: str = "default"):
        import jax.numpy as jnp

        # Own key, NOT "strength": stock keeps area strength and mask
        # strength separate and MULTIPLIES them (get_area_and_mult) — a
        # shared key would have SetArea/SetMask clobber each other.
        tag = {"mask": jnp.asarray(mask, jnp.float32),
               "mask_strength": float(strength)}
        return (_tag_all_entries(conditioning, tag),)


class VAEDecodeTiled:
    """Stock tiled decode: bounded activation memory at any resolution.
    ``tile_size`` is in PIXELS like stock (converted to latent cells by the
    VAE's spatial factor); the tile/overlap policy itself lives with its
    single owner, ``models/vae.decode_maybe_tiled``. Stock's newer
    ``overlap``/``temporal_size``/``temporal_overlap`` widgets are accepted
    so current exports run unchanged — overlap is owner-derived and the
    temporal knobs don't apply to spatial tiling here."""

    DESCRIPTION = "Stock-name tiled VAE decode."
    RETURN_TYPES = ("IMAGE",)
    RETURN_NAMES = ("image",)
    FUNCTION = "decode"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "samples": ("LATENT", {}),
                "vae": ("VAE", {}),
                "tile_size": ("INT", {"default": 512, "min": 64, "max": 4096,
                                      "step": 32}),
            },
            "optional": {
                "overlap": ("INT", {"default": 64, "min": 0, "max": 4096}),
                "temporal_size": ("INT", {"default": 64, "min": 8,
                                          "max": 4096}),
                "temporal_overlap": ("INT", {"default": 8, "min": 4,
                                             "max": 4096}),
            },
        }

    def decode(self, samples, vae, tile_size: int = 512, overlap: int = 64,
               temporal_size: int = 64, temporal_overlap: int = 8):
        from .models.vae import decode_maybe_tiled, vae_output_to_images

        factor = getattr(vae, "spatial_factor", 8)
        tile = max(8, int(tile_size) // factor)
        return (vae_output_to_images(
            decode_maybe_tiled(vae, samples["samples"], tile)
        ),)


class VAEEncodeTiled:
    """Stock tiled encode — the img2img counterpart of VAEDecodeTiled for
    resolutions whose encoder activations exceed HBM. Tile/overlap policy via
    its owner ``models/vae.encode_maybe_tiled`` (pixel-unit tile, overlap
    floored to the VAE's spatial-factor alignment)."""

    DESCRIPTION = "Stock-name tiled VAE encode."
    RETURN_TYPES = ("LATENT",)
    RETURN_NAMES = ("latent",)
    FUNCTION = "encode"
    CATEGORY = CATEGORY

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "pixels": ("IMAGE", {}),
                "vae": ("VAE", {}),
                "tile_size": ("INT", {"default": 512, "min": 64, "max": 4096,
                                      "step": 64}),
            },
            "optional": {
                "overlap": ("INT", {"default": 64, "min": 0, "max": 4096}),
                "temporal_size": ("INT", {"default": 64, "min": 8,
                                          "max": 4096}),
                "temporal_overlap": ("INT", {"default": 8, "min": 4,
                                             "max": 4096}),
            },
        }

    def encode(self, pixels, vae, tile_size: int = 512, overlap: int = 64,
               temporal_size: int = 64, temporal_overlap: int = 8):
        import jax.numpy as jnp

        from .models.vae import encode_maybe_tiled, images_to_vae_input

        img = jnp.asarray(pixels)
        if img.ndim == 3:
            img = img[None]
        z = encode_maybe_tiled(vae, images_to_vae_input(img), int(tile_size))
        return ({"samples": z},)


def stock_node_mappings() -> dict[str, type]:
    """All stock-name shims, keyed by the stock class name (merged into
    ``nodes.NODE_CLASS_MAPPINGS`` so exported workflows resolve directly)."""
    from . import nodes as n

    LoadImage.RETURN_TYPES = n.TPULoadImage.RETURN_TYPES
    LoadImage.RETURN_NAMES = getattr(n.TPULoadImage, "RETURN_NAMES", None)

    mappings = {
        "CheckpointLoaderSimple": CheckpointLoaderSimple,
        "DualCLIPLoader": DualCLIPLoader,
        "CLIPLoader": CLIPLoader,
        "TripleCLIPLoader": TripleCLIPLoader,
        "VAELoader": VAELoader,
        "UNETLoader": UNETLoader,
        "unCLIPConditioning": unCLIPConditioning,
        "LoraLoader": LoraLoader,
        "LoraLoaderModelOnly": LoraLoaderModelOnly,
        "CLIPSetLastLayer": CLIPSetLastLayer,
        "LoadImage": LoadImage,
        "LatentUpscale": LatentUpscale,
        # Pure renames.
        "CLIPTextEncode": _renamed(n.TPUTextEncode, {}, name="CLIPTextEncode"),
        "EmptyLatentImage": _renamed(
            n.TPUEmptyLatent, {}, name="EmptyLatentImage"
        ),
        "EmptySD3LatentImage": _EmptyLatent16ch,
        "KSampler": _renamed(
            n.TPUKSampler, {"latent_image": "latent"}, name="KSampler"
        ),
        "KSamplerAdvanced": _renamed(
            n.TPUKSamplerAdvanced, {}, name="KSamplerAdvanced"
        ),
        "VAEDecode": _renamed(
            n.TPUVAEDecode, {"samples": "latent"}, name="VAEDecode"
        ),
        "VAEEncode": _renamed(
            n.TPUVAEEncode, {"pixels": "image"}, name="VAEEncode"
        ),
        "SaveImage": _renamed(n.TPUSaveImage, {}, name="SaveImage"),
        "ImageScale": ImageScale,
        "ImageScaleBy": ImageScaleBy,
        "PreviewImage": PreviewImage,
        "ConditioningCombine": ConditioningCombine,
        "ConditioningSetArea": ConditioningSetArea,
        "ConditioningSetMask": ConditioningSetMask,
        "ConditioningSetAreaPercentage": ConditioningSetAreaPercentage,
        "CLIPTextEncodeFlux": CLIPTextEncodeFlux,
        "FreeU": FreeU,
        "FreeU_V2": FreeU_V2,
        "RescaleCFG": RescaleCFG,
        "ModelSamplingDiscrete": ModelSamplingDiscrete,
        "ModelSamplingSD3": ModelSamplingSD3,
        "ModelSamplingAuraFlow": ModelSamplingAuraFlow,
        "ModelSamplingFlux": ModelSamplingFlux,
        "unCLIPCheckpointLoader": unCLIPCheckpointLoader,
        "SamplerCustom": SamplerCustom,
        "ImageCrop": ImageCrop,
        "ImageScaleToTotalPixels": ImageScaleToTotalPixels,
        "ModelMergeSimple": ModelMergeSimple,
        "ImageBlur": ImageBlur,
        "ImageSharpen": ImageSharpen,
        "LatentBlend": LatentBlend,
        "LatentBatch": LatentBatch,
        "LatentAdd": _latent_binop("LatentAdd", lambda a, b: a + b),
        "LatentSubtract": _latent_binop("LatentSubtract", lambda a, b: a - b),
        "LatentInterpolate": LatentInterpolate,
        "LatentMultiply": LatentMultiply,
        "KarrasScheduler": KarrasScheduler,
        "ExponentialScheduler": ExponentialScheduler,
        "SDTurboScheduler": SDTurboScheduler,
        "SamplerEulerAncestral": _named_sampler("SamplerEulerAncestral",
                                                "euler_ancestral"),
        "SamplerDPMPP_2M_SDE": _named_sampler("SamplerDPMPP_2M_SDE",
                                              "dpmpp_2m_sde"),
        "SamplerDPMPP_SDE": _named_sampler("SamplerDPMPP_SDE", "dpmpp_sde"),
        "SamplerDPMPP_3M_SDE": _named_sampler("SamplerDPMPP_3M_SDE",
                                              "dpmpp_3m_sde"),
        "SamplerLMS": _named_sampler("SamplerLMS", "lms"),
        "EmptyHunyuanLatentVideo": EmptyHunyuanLatentVideo,
        "ConditioningAverage": ConditioningAverage,
        "ConditioningZeroOut": ConditioningZeroOut,
        "ConditioningSetTimestepRange": ConditioningSetTimestepRange,
        "ConditioningConcat": ConditioningConcat,
        "CLIPTextEncodeSDXL": CLIPTextEncodeSDXL,
        "CLIPTextEncodeSDXLRefiner": CLIPTextEncodeSDXLRefiner,
        "ImageInvert": ImageInvert,
        "ImageBatch": ImageBatch,
        "RepeatLatentBatch": RepeatLatentBatch,
        "LatentFromBatch": LatentFromBatch,
        "LatentFlip": LatentFlip,
        "LatentRotate": LatentRotate,
        "LatentCrop": LatentCrop,
        "SaveLatent": SaveLatent,
        "LoadLatent": LoadLatent,
        "SolidMask": SolidMask,
        "InvertMask": InvertMask,
        "ImageToMask": ImageToMask,
        "MaskToImage": MaskToImage,
        "GrowMask": GrowMask,
        "FeatherMask": FeatherMask,
        "MaskComposite": MaskComposite,
        "LoadImageMask": LoadImageMask,
        "VAEEncodeForInpaint": VAEEncodeForInpaint,
        "VAEDecodeTiled": VAEDecodeTiled,
        "VAEEncodeTiled": VAEEncodeTiled,
        "ImagePadForOutpaint": ImagePadForOutpaint,
        "ImageCompositeMasked": ImageCompositeMasked,
        "LatentComposite": LatentComposite,
        "SaveAnimatedWEBP": SaveAnimatedWEBP,
        "ControlNetLoader": ControlNetLoader,
        "ControlNetApply": ControlNetApply,
        "ControlNetApplyAdvanced": ControlNetApplyAdvanced,
        "CLIPVisionLoader": CLIPVisionLoader,
        "CLIPVisionEncode": CLIPVisionEncode,
        "WanImageToVideo": WanImageToVideo,
        "UpscaleModelLoader": UpscaleModelLoader,
        "ImageUpscaleWithModel": _renamed(
            n.TPUImageUpscaleWithModel, {}, name="ImageUpscaleWithModel"
        ),
        # Stock-shaped from the start (same widget names).
        "InpaintModelConditioning": _renamed(
            n.TPUInpaintModelConditioning, {}, name="InpaintModelConditioning"
        ),
        "LatentUpscaleBy": _renamed(
            n.TPULatentUpscale, {"samples": "latent", "scale_by": "scale",
                                 "upscale_method": "method"},
            name="LatentUpscaleBy",
        ),
        "SetLatentNoiseMask": _renamed(
            n.TPUSetLatentNoiseMask, {"samples": "latent"},
            name="SetLatentNoiseMask",
        ),
        # Custom-sampling family: built stock-shaped from the start.
        "RandomNoise": _renamed(n.TPURandomNoise, {}, name="RandomNoise"),
        "DisableNoise": _renamed(n.TPUDisableNoise, {}, name="DisableNoise"),
        "KSamplerSelect": _renamed(
            n.TPUKSamplerSelect, {}, name="KSamplerSelect"
        ),
        "BasicScheduler": _renamed(
            n.TPUBasicScheduler, {}, name="BasicScheduler"
        ),
        "BasicGuider": _renamed(n.TPUBasicGuider, {}, name="BasicGuider"),
        "CFGGuider": _renamed(n.TPUCFGGuider, {}, name="CFGGuider"),
        "FluxGuidance": _renamed(n.TPUFluxGuidance, {}, name="FluxGuidance"),
        "SamplerCustomAdvanced": _renamed(
            n.TPUSamplerCustomAdvanced, {}, name="SamplerCustomAdvanced"
        ),
        "SplitSigmas": _renamed(n.TPUSplitSigmas, {}, name="SplitSigmas"),
        "FlipSigmas": _renamed(n.TPUFlipSigmas, {}, name="FlipSigmas"),
    }
    return mappings


def register(
    node_class_mappings: dict[str, type],
    display_name_mappings: dict[str, str] | None = None,
) -> None:
    """Merge the shims into a registry without overriding native names."""
    for name, cls in stock_node_mappings().items():
        node_class_mappings.setdefault(name, cls)
        if display_name_mappings is not None:
            display_name_mappings.setdefault(name, f"{name} (stock compat)")
