"""Stock-ComfyUI node-name shims (nodes_compat.py): a workflow exported from
a stock ComfyUI install — builtin class names, builtin input keys — runs
against this host unchanged.

The reference pack lives inside ComfyUI and gets the builtins for free
(any_device_parallel.py:1473-1483 registers only its own nodes); here the
builtin names are part of the host-parity surface. Family sniffing
(models/loader.sniff_model_family) replaces the stock loader's implicit
config detection.
"""

import json
import os

import numpy as np
import pytest

from comfyui_parallelanything_tpu.host import run_workflow
from comfyui_parallelanything_tpu.models.loader import sniff_model_family


class TestSniffModelFamily:
    def _flux_keys(self, dev=True, depth=19):
        sd = {f"double_blocks.{i}.img_attn.qkv.weight": np.zeros((1, 1))
              for i in range(depth)}
        sd["single_blocks.0.linear1.weight"] = np.zeros((1, 1))
        if dev:
            sd["guidance_in.in_layer.weight"] = np.zeros((1, 1))
        return sd

    def test_flux_dev_vs_schnell_vs_zimage(self):
        assert sniff_model_family(self._flux_keys(dev=True)) == "flux-dev"
        assert sniff_model_family(self._flux_keys(dev=False)) == "flux-schnell"
        # A FLUX-layout file without a guidance embedder is a (possibly
        # depth-cut) schnell whatever its depths: no FLUX-class shape carries
        # Z-Image's name (PR 34; until then 6 double blocks sniffed as it).
        assert sniff_model_family(
            self._flux_keys(dev=False, depth=6)
        ) == "flux-schnell"
        # Z-Image is its published single-stream layout: refiner stacks and a
        # caption embedder beside ``layers``, bare or prefixed.
        zimage = {k: np.zeros((1, 1)) for k in (
            "layers.0.attention.to_q.weight", "noise_refiner.0.attention.to_q.weight",
            "context_refiner.0.attention.to_q.weight", "cap_embedder.1.weight")}
        assert sniff_model_family(zimage) == "zimage-turbo"
        assert sniff_model_family(
            {f"model.diffusion_model.{k}": v for k, v in zimage.items()}
        ) == "zimage-turbo"

    def test_prefixed_full_checkpoint_keys(self):
        sd = {f"model.diffusion_model.{k}": v
              for k, v in self._flux_keys().items()}
        sd["first_stage_model.decoder.conv_in.weight"] = np.zeros((1, 1))
        assert sniff_model_family(sd) == "flux-dev"

    def test_mmdit_variants(self):
        base = {f"joint_blocks.{i}.x_block.attn.qkv.weight": np.zeros((1, 1))
                for i in range(24)}
        assert sniff_model_family(base) == "sd3-medium"
        large = {f"joint_blocks.{i}.x_block.attn.qkv.weight": np.zeros((1, 1))
                 for i in range(38)}
        assert sniff_model_family(large) == "sd35-large"
        dual = dict(base)
        dual["joint_blocks.0.x_block.attn2.qkv.weight"] = np.zeros((1, 1))
        assert sniff_model_family(dual) == "sd35-medium"

    def test_wan_width(self):
        sd = {"blocks.0.self_attn.q.weight": np.zeros((1536, 1536))}
        assert sniff_model_family(sd) == "wan-1.3b"
        sd = {"blocks.0.self_attn.q.weight": np.zeros((5120, 5120))}
        assert sniff_model_family(sd) == "wan-14b"

    def test_unet_families(self):
        sdxl = {"input_blocks.0.0.weight": np.zeros((1, 1)),
                "label_emb.0.0.weight": np.zeros((1, 1))}
        assert sniff_model_family(sdxl) == "sdxl"
        sd15 = {
            "input_blocks.0.0.weight": np.zeros((1, 1)),
            "input_blocks.1.1.transformer_blocks.0.attn2.to_k.weight":
                np.zeros((320, 768)),
        }
        assert sniff_model_family(sd15) == "sd15"
        sd21 = {
            "input_blocks.0.0.weight": np.zeros((1, 1)),
            "input_blocks.1.1.transformer_blocks.0.attn2.to_k.weight":
                np.zeros((320, 1024)),
        }
        assert sniff_model_family(sd21) == "sd21"

    def test_unknown_raises(self):
        with pytest.raises(ValueError, match="cannot sniff"):
            sniff_model_family({"some.random.weight": np.zeros((1,))})

    def test_sniffs_synthetic_sd15_checkpoint(self, tmp_path, monkeypatch):
        # The same synthetic checkpoint the e2e test loads must sniff sd15.
        paths = _synthetic_stock_env(tmp_path, monkeypatch)
        from comfyui_parallelanything_tpu.models import load_safetensors

        assert sniff_model_family(load_safetensors(paths["ckpt"])) == "sd15"


def _synthetic_stock_env(tmp_path, monkeypatch):
    """Tiny sd15 checkpoint WITH bundled cond_stage_model CLIP (the stock
    loader extracts text encoders from the file), plus tokenizer tables wired
    through the PA_* env vars the shims read. Mirrors
    test_host_graph._synthetic_env, extended with the bundled tower."""
    import jax
    import jax.numpy as jnp
    from safetensors.numpy import save_file

    import comfyui_parallelanything_tpu.models as models_pkg
    import comfyui_parallelanything_tpu.models.text_encoders as te_mod
    from comfyui_parallelanything_tpu.models import build_unet, build_vae
    from tests.test_convert_unet import _ldm_sd
    from tests.test_text_encoders import TINY_CLIP, _hf_clip
    from tests.test_vae import TINY as TINY_VAE, _ldm_layout_sd

    real_sd15 = models_pkg.sd15_config

    def tiny_sd15():
        return real_sd15(
            model_channels=32, channel_mult=(1, 2), transformer_depth=(1, 1),
            attention_levels=(0, 1), context_dim=TINY_CLIP.hidden_size,
            num_heads=4, norm_groups=8, dtype=jnp.float32,
        )

    monkeypatch.setattr(models_pkg, "sd15_config", tiny_sd15)
    monkeypatch.setattr(models_pkg, "sd_vae_config", lambda: TINY_VAE)
    monkeypatch.setattr(te_mod, "clip_l_config", lambda: TINY_CLIP)

    ucfg = tiny_sd15()
    unet = build_unet(ucfg, jax.random.key(0), sample_shape=(1, 8, 8, 4))
    vae = build_vae(TINY_VAE, jax.random.key(1), sample_hw=16)
    hf = _hf_clip(TINY_CLIP, "quick_gelu")
    sd = {
        f"model.diffusion_model.{k}": np.ascontiguousarray(v)
        for k, v in _ldm_sd(ucfg, unet.params).items()
    }
    sd.update({
        f"first_stage_model.{k}": np.ascontiguousarray(v)
        for k, v in _ldm_layout_sd(TINY_VAE, vae.params).items()
    })
    # Bundled text tower, SD1.x layout: cond_stage_model.transformer.<HF keys>.
    sd.update({
        f"cond_stage_model.transformer.{k}":
            np.ascontiguousarray(v.detach().numpy())
        for k, v in hf.state_dict().items()
    })
    ckpt = tmp_path / "ckpt.safetensors"
    save_file(sd, str(ckpt))

    tok_path = _word_level_tokenizer(tmp_path, monkeypatch)
    return {"ckpt": str(ckpt), "tok": tok_path}


def _word_level_tokenizer(tmp_path, monkeypatch) -> str:
    tokenizers = pytest.importorskip("tokenizers")
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace

    vocab = {"[UNK]": 0, "a": 5, "watercolor": 6, "lighthouse": 7, "at": 8,
             "dawn": 9, "blurry": 10, "low": 11, "quality": 12}
    t = tokenizers.Tokenizer(WordLevel(vocab, unk_token="[UNK]"))
    t.pre_tokenizer = Whitespace()
    tok_path = tmp_path / "tokenizer.json"
    t.save(str(tok_path))

    monkeypatch.setenv("PA_TOKENIZER_JSON", str(tok_path))
    return str(tok_path)


def _synthetic_sdxl_env(tmp_path, monkeypatch):
    """Tiny single-file SDXL checkpoint with BOTH bundled conditioner towers
    (HF CLIP-L under conditioner.embedders.0, OpenCLIP-G under
    conditioner.embedders.1) plus the VAE — the stock SDXL export layout,
    sniffed as family=sdxl by CheckpointLoaderSimple. The tiny widths are
    coupled the way the real family's are: context = L ⊕ G hidden,
    adm = G pooled + 6×256 size embeddings."""
    import jax
    import jax.numpy as jnp
    from safetensors.numpy import save_file

    import comfyui_parallelanything_tpu.models as models_pkg
    from comfyui_parallelanything_tpu.models import build_unet, build_vae
    from comfyui_parallelanything_tpu.models.text_encoders import (
        build_clip_text,
        open_clip_g_config,
    )
    from tests.test_convert_unet import _ldm_sd
    from tests.test_text_encoders import (
        TINY_CLIP,
        TestOpenCLIPConversion,
        _hf_clip,
    )
    from tests.test_vae import TINY as TINY_VAE, _ldm_layout_sd

    g_cfg = open_clip_g_config(
        vocab_size=100, hidden_size=64, num_layers=2, num_heads=4,
        max_len=16, projection_dim=64, dtype=jnp.float32,
    )
    real_xl = models_pkg.sdxl_config

    def tiny_xl():
        return real_xl(
            model_channels=32, channel_mult=(1, 2), num_res_blocks=1,
            attention_levels=(1,), transformer_depth=(0, 1), num_heads=4,
            context_dim=TINY_CLIP.hidden_size + g_cfg.hidden_size,
            adm_in_channels=g_cfg.projection_dim + 6 * 256,
            norm_groups=8, dtype=jnp.float32,
        )

    import comfyui_parallelanything_tpu.models.text_encoders as te_mod

    monkeypatch.setattr(models_pkg, "sdxl_config", tiny_xl)
    monkeypatch.setattr(models_pkg, "sdxl_vae_config", lambda: TINY_VAE)
    monkeypatch.setattr(models_pkg, "open_clip_g_config", lambda: g_cfg)
    monkeypatch.setattr(te_mod, "clip_l_config", lambda: TINY_CLIP)

    ucfg = tiny_xl()
    unet = build_unet(ucfg, jax.random.key(0), sample_shape=(1, 8, 8, 4))
    vae = build_vae(TINY_VAE, jax.random.key(1), sample_hw=16)
    hf = _hf_clip(TINY_CLIP, "quick_gelu")
    g_enc = build_clip_text(g_cfg, rng=jax.random.key(2))
    sd = {
        f"model.diffusion_model.{k}": np.ascontiguousarray(v)
        for k, v in _ldm_sd(ucfg, unet.params).items()
    }
    sd.update({
        f"first_stage_model.{k}": np.ascontiguousarray(v)
        for k, v in _ldm_layout_sd(TINY_VAE, vae.params).items()
    })
    sd.update({
        f"conditioner.embedders.0.transformer.{k}":
            np.ascontiguousarray(v.detach().numpy())
        for k, v in hf.state_dict().items()
    })
    sd.update({
        f"conditioner.embedders.1.model.{k}": np.ascontiguousarray(v)
        for k, v in TestOpenCLIPConversion._openclip_layout(
            g_cfg, g_enc.params
        ).items()
    })
    ckpt = tmp_path / "sdxl_ckpt.safetensors"
    save_file(sd, str(ckpt))
    tok_path = _word_level_tokenizer(tmp_path, monkeypatch)
    return {"ckpt": str(ckpt), "tok": tok_path}


def _synthetic_refiner_env(tmp_path, monkeypatch):
    """Tiny SDXL-REFINER single-file checkpoint: refiner-shaped UNet (no
    deepest-level attention, depth-carrying middle transformer, G-only
    1280-wide context so the family SNIFFS as sdxl-refiner), the bundled
    OpenCLIP-G tower under conditioner.embedders.0.model.*, and the VAE."""
    import jax
    import jax.numpy as jnp
    from safetensors.numpy import save_file

    import comfyui_parallelanything_tpu.models as models_pkg
    from comfyui_parallelanything_tpu.models import build_unet, build_vae
    from comfyui_parallelanything_tpu.models.text_encoders import (
        build_clip_text,
        open_clip_g_config,
    )
    from tests.test_convert_unet import _ldm_sd
    from tests.test_text_encoders import TestOpenCLIPConversion
    from tests.test_vae import TINY as TINY_VAE, _ldm_layout_sd

    g_cfg = open_clip_g_config(
        vocab_size=100, hidden_size=1280, num_layers=1, num_heads=8,
        max_len=16, intermediate_size=128, projection_dim=64,
        dtype=jnp.float32,
    )
    real_ref = models_pkg.sdxl_refiner_config

    def tiny_refiner():
        return real_ref(
            model_channels=32, channel_mult=(1, 2), num_res_blocks=1,
            attention_levels=(1,), transformer_depth=(0, 1),
            transformer_depth_middle=1, num_heads=4,
            context_dim=g_cfg.hidden_size,
            adm_in_channels=g_cfg.projection_dim + 5 * 256,
            norm_groups=8, dtype=jnp.float32,
        )

    monkeypatch.setattr(models_pkg, "sdxl_refiner_config", tiny_refiner)
    monkeypatch.setattr(models_pkg, "sdxl_vae_config", lambda: TINY_VAE)
    monkeypatch.setattr(models_pkg, "open_clip_g_config", lambda: g_cfg)

    ucfg = tiny_refiner()
    unet = build_unet(ucfg, jax.random.key(0), sample_shape=(1, 8, 8, 4))
    vae = build_vae(TINY_VAE, jax.random.key(1), sample_hw=16)
    g_enc = build_clip_text(g_cfg, rng=jax.random.key(2))
    sd = {
        f"model.diffusion_model.{k}": np.ascontiguousarray(v)
        for k, v in _ldm_sd(ucfg, unet.params).items()
    }
    sd.update({
        f"first_stage_model.{k}": np.ascontiguousarray(v)
        for k, v in _ldm_layout_sd(TINY_VAE, vae.params).items()
    })
    sd.update({
        f"conditioner.embedders.0.model.{k}": np.ascontiguousarray(v)
        for k, v in TestOpenCLIPConversion._openclip_layout(
            g_cfg, g_enc.params
        ).items()
    })
    ckpt = tmp_path / "refiner_ckpt.safetensors"
    save_file(sd, str(ckpt))
    tok_path = _word_level_tokenizer(tmp_path, monkeypatch)
    return {"ckpt": str(ckpt), "tok": tok_path}


class TestStockWorkflow:
    def _stock_workflow(self, ckpt):
        """API-format graph exactly as a stock ComfyUI export writes it:
        builtin class names, builtin input keys, [node, output] links."""
        return {
            "4": {"class_type": "CheckpointLoaderSimple",
                  "inputs": {"ckpt_name": ckpt}},
            "5": {"class_type": "EmptyLatentImage",
                  "inputs": {"width": 32, "height": 32, "batch_size": 2}},
            "6": {"class_type": "CLIPTextEncode",
                  "inputs": {"text": "a watercolor lighthouse at dawn",
                             "clip": ["4", 1]}},
            "7": {"class_type": "CLIPTextEncode",
                  "inputs": {"text": "blurry low quality",
                             "clip": ["4", 1]}},
            # seed beyond 2**63: stock seed widgets are 64-bit and the UI's
            # randomize fills [0, 2**64) — half of exported workflows carry a
            # seed jax.random.key would reject (ADVICE r3, folded by seed_key).
            "3": {"class_type": "KSampler",
                  "inputs": {"seed": 2**63 + 7, "steps": 2, "cfg": 7.0,
                             "sampler_name": "euler", "scheduler": "normal",
                             "denoise": 1.0, "model": ["4", 0],
                             "positive": ["6", 0], "negative": ["7", 0],
                             "latent_image": ["5", 0]}},
            "8": {"class_type": "VAEDecode",
                  "inputs": {"samples": ["3", 0], "vae": ["4", 2]}},
            "9": {"class_type": "SaveImage",
                  "inputs": {"images": ["8", 0],
                             "filename_prefix": "ComfyUI"}},
        }

    def test_exported_stock_workflow_runs_unchanged(self, tmp_path, monkeypatch):
        paths = _synthetic_stock_env(tmp_path, monkeypatch)
        monkeypatch.setenv("PA_OUTPUT_DIR", str(tmp_path / "out"))
        wf = self._stock_workflow(paths["ckpt"])
        # SaveImage's stock form has no output_dir widget; point the TPU
        # node's default there via its own optional input (exported graphs
        # carry only filename_prefix — add output_dir like a host config).
        wf["9"]["inputs"]["output_dir"] = str(tmp_path / "out")

        out = run_workflow(wf)
        images = out["8"][0]
        assert images.shape[0] == 2 and images.shape[-1] == 3
        assert np.isfinite(np.asarray(images)).all()
        saved = out["9"][0]
        assert len(saved) == 2 and all(os.path.exists(p) for p in saved)

    def test_stock_conditioning_and_image_shims_run(self, tmp_path,
                                                    monkeypatch):
        # regional prompting (SetArea → Combine),
        # prompt blending (Average), stock image resize, and PreviewImage —
        # one exported-style graph exercising all of them.
        paths = _synthetic_stock_env(tmp_path, monkeypatch)
        monkeypatch.setenv("PA_OUTPUT_DIR", str(tmp_path / "out"))
        wf = self._stock_workflow(paths["ckpt"])
        wf["9"]["inputs"]["output_dir"] = str(tmp_path / "out")
        wf.update({
            "10": {"class_type": "CLIPTextEncode",
                   "inputs": {"text": "blurry low quality",
                              "clip": ["4", 1]}},
            # Regional prompt: the second prompt scoped to the top-left 16px
            # (2 latent cells of the 32px graph), combined into the first.
            "11": {"class_type": "ConditioningSetArea",
                   "inputs": {"conditioning": ["10", 0], "width": 16,
                              "height": 16, "x": 0, "y": 0, "strength": 0.8}},
            "12": {"class_type": "ConditioningCombine",
                   "inputs": {"conditioning_1": ["6", 0],
                              "conditioning_2": ["11", 0]}},
            # Blend the two raw prompts too (exercises Average's lerp).
            "13": {"class_type": "ConditioningAverage",
                   "inputs": {"conditioning_to": ["12", 0],
                              "conditioning_from": ["10", 0],
                              "conditioning_to_strength": 0.7}},
            "14": {"class_type": "ImageScale",
                   "inputs": {"image": ["8", 0], "upscale_method": "bicubic",
                              "width": 48, "height": 40, "crop": "center"}},
            "15": {"class_type": "ImageScaleBy",
                   "inputs": {"image": ["8", 0],
                              "upscale_method": "lanczos", "scale_by": 0.5}},
            "16": {"class_type": "PreviewImage",
                   "inputs": {"images": ["14", 0]}},
        })
        wf["3"]["inputs"]["positive"] = ["13", 0]

        out = run_workflow(wf)
        assert np.isfinite(np.asarray(out["8"][0])).all()
        assert out["14"][0].shape[1:3] == (40, 48)
        h, w = np.asarray(out["8"][0]).shape[1:3]
        assert out["15"][0].shape[1:3] == (
            max(1, round(h * 0.5)), max(1, round(w * 0.5)))
        # Stock 0-sentinel: a zero dim keeps the source aspect ratio.
        from comfyui_parallelanything_tpu.nodes_compat import ImageScale

        (kept,) = ImageScale().upscale(
            np.zeros((1, 10, 20, 3), np.float32), "bilinear",
            width=40, height=0,
        )
        assert kept.shape[1:3] == (20, 40)
        with pytest.raises(ValueError, match="both be 0"):
            ImageScale().upscale(
                np.zeros((1, 10, 20, 3), np.float32), "bilinear",
                width=0, height=0,
            )
        previews = out["16"][0]
        assert previews and all(os.path.exists(p) for p in previews)
        assert all(os.sep + "temp" + os.sep in p for p in previews)

    def test_conditioning_zero_out_and_sdxl_encode(self, tmp_path,
                                                   monkeypatch):
        import jax.numpy as jnp

        from comfyui_parallelanything_tpu.nodes import NODE_CLASS_MAPPINGS

        paths = _synthetic_stock_env(tmp_path, monkeypatch)
        _, clip, _ = (
            NODE_CLASS_MAPPINGS["CheckpointLoaderSimple"]().load(paths["ckpt"])
        )
        enc = NODE_CLASS_MAPPINGS["CLIPTextEncode"]()
        (cond,) = enc.run(clip=clip, text="a watercolor lighthouse")

        # ZeroOut: every embedding zeroed, extras included.
        zo = NODE_CLASS_MAPPINGS["ConditioningZeroOut"]()
        (z,) = zo.zero_out({**cond, "extras": (dict(cond),)})
        assert float(jnp.abs(z["context"]).max()) == 0.0
        assert float(jnp.abs(z["extras"][0]["context"]).max()) == 0.0
        assert z["context"].shape == cond["context"].shape

        # CLIPTextEncodeSDXL over a dual wire (same tiny tower as both L and
        # G — the shim's plumbing and the 2816-style size vector are what's
        # under test, not tower asymmetry).
        dual = {"type": "sdxl-dual", "l": clip, "g": clip}
        xl = NODE_CLASS_MAPPINGS["CLIPTextEncodeSDXL"]()
        (c,) = xl.encode(
            dual, width=512, height=768, crop_w=0, crop_h=0,
            target_width=1024, target_height=1024,
            text_g="a watercolor lighthouse", text_l="at dawn",
        )
        hidden = cond["penultimate"].shape[-1]
        assert c["context"].shape[-1] == 2 * hidden
        assert c["pooled"].shape[-1] == cond["pooled"].shape[-1] + 6 * 256
        with pytest.raises(ValueError, match="dual"):
            xl.encode(clip, 512, 512, 0, 0, 512, 512, "a", "b")

    def test_models_dir_resolution(self, tmp_path, monkeypatch):
        # ComfyUI folder layout: a bare name resolves via
        # $PA_MODELS_DIR/checkpoints/<name>.
        paths = _synthetic_stock_env(tmp_path, monkeypatch)
        models = tmp_path / "models" / "checkpoints"
        models.mkdir(parents=True)
        os.rename(paths["ckpt"], models / "tiny.safetensors")
        monkeypatch.setenv("PA_MODELS_DIR", str(tmp_path / "models"))

        wf = self._stock_workflow("tiny.safetensors")
        del wf["9"]  # no image save needed for the resolution check
        out = run_workflow(wf)
        assert out["8"][0].shape[0] == 2

    def test_clip_set_last_layer_tags_wire(self, tmp_path, monkeypatch):
        paths = _synthetic_stock_env(tmp_path, monkeypatch)
        wf = self._stock_workflow(paths["ckpt"])
        del wf["9"]
        wf["10"] = {"class_type": "CLIPSetLastLayer",
                    "inputs": {"clip": ["4", 1], "stop_at_clip_layer": -2}}
        wf["6"]["inputs"]["clip"] = ["10", 0]
        out = run_workflow(wf)
        assert np.isfinite(np.asarray(out["8"][0])).all()

    def test_missing_tokenizer_fails_with_instructions(self, tmp_path,
                                                       monkeypatch):
        paths = _synthetic_stock_env(tmp_path, monkeypatch)
        monkeypatch.delenv("PA_TOKENIZER_JSON")
        wf = self._stock_workflow(paths["ckpt"])
        with pytest.raises(Exception, match="PA_TOKENIZER_JSON"):
            run_workflow(wf)

    def test_stock_custom_sampling_graph_executes(self, tmp_path, monkeypatch):
        # The custom-sampling path exactly as a stock FLUX-style export wires
        # it: RandomNoise + KSamplerSelect + BasicScheduler + CFGGuider +
        # SamplerCustomAdvanced under their stock names and stock input keys.
        paths = _synthetic_stock_env(tmp_path, monkeypatch)
        wf = {
            "ckpt": {"class_type": "CheckpointLoaderSimple",
                     "inputs": {"ckpt_name": paths["ckpt"]}},
            "pos": {"class_type": "CLIPTextEncode",
                    "inputs": {"text": "a watercolor lighthouse",
                               "clip": ["ckpt", 1]}},
            "neg": {"class_type": "CLIPTextEncode",
                    "inputs": {"text": "blurry", "clip": ["ckpt", 1]}},
            "latent": {"class_type": "EmptyLatentImage",
                       "inputs": {"width": 32, "height": 32, "batch_size": 1}},
            "noise": {"class_type": "RandomNoise",
                      "inputs": {"noise_seed": 11}},
            "sel": {"class_type": "KSamplerSelect",
                    "inputs": {"sampler_name": "euler"}},
            "sig": {"class_type": "BasicScheduler",
                    "inputs": {"model": ["ckpt", 0], "scheduler": "normal",
                               "steps": 2, "denoise": 1.0}},
            "guide": {"class_type": "CFGGuider",
                      "inputs": {"model": ["ckpt", 0], "positive": ["pos", 0],
                                 "negative": ["neg", 0], "cfg": 3.0}},
            "run": {"class_type": "SamplerCustomAdvanced",
                    "inputs": {"noise": ["noise", 0], "guider": ["guide", 0],
                               "sampler": ["sel", 0], "sigmas": ["sig", 0],
                               "latent_image": ["latent", 0]}},
            "dec": {"class_type": "VAEDecode",
                    "inputs": {"samples": ["run", 0], "vae": ["ckpt", 2]}},
        }
        out = run_workflow(wf)
        images = out["dec"][0]
        assert images.shape[0] == 1 and images.shape[-1] == 3
        assert np.isfinite(np.asarray(images)).all()

    def test_latent_upscale_absolute_dims(self, tmp_path, monkeypatch):
        from comfyui_parallelanything_tpu.nodes import NODE_CLASS_MAPPINGS

        lat = {"samples": np.zeros((1, 8, 8, 4), np.float32)}
        node = NODE_CLASS_MAPPINGS["LatentUpscale"]()
        (out,) = node.upscale(lat, "bilinear", width=128, height=128)
        # 128 px -> 16 latent; from 8 -> scale 2.
        assert out["samples"].shape == (1, 16, 16, 4)
        # Width-only change must NOT no-op: axes scale independently.
        (wide,) = node.upscale(lat, "bilinear", width=192, height=64)
        assert wide["samples"].shape == (1, 8, 24, 4)

    def test_lora_loader_rebakes_from_source(self, tmp_path, monkeypatch):
        from safetensors.numpy import save_file

        from comfyui_parallelanything_tpu.models import load_safetensors
        from comfyui_parallelanything_tpu.nodes import NODE_CLASS_MAPPINGS

        paths = _synthetic_stock_env(tmp_path, monkeypatch)
        model, clip, vae = (
            NODE_CLASS_MAPPINGS["CheckpointLoaderSimple"]().load(paths["ckpt"])
        )

        # Rank-2 kohya LoRA against a real attention projection of the tiny
        # checkpoint (bake_lora matches the stripped ldm key).
        sd = load_safetensors(paths["ckpt"])
        target = next(
            k for k in sd
            if k.endswith("attn1.to_q.weight") and "input_blocks" in k
        ).removeprefix("model.diffusion_model.")
        out_d, in_d = sd[f"model.diffusion_model.{target}"].shape
        rng = np.random.default_rng(5)
        lora_path = tmp_path / "style.safetensors"
        save_file({
            f"{target.removesuffix('.weight')}.lora_down.weight":
                rng.standard_normal((2, in_d)).astype(np.float32),
            f"{target.removesuffix('.weight')}.lora_up.weight":
                rng.standard_normal((out_d, 2)).astype(np.float32),
        }, str(lora_path))

        node = NODE_CLASS_MAPPINGS["LoraLoader"]()
        patched, clip_out = node.load_lora(model, clip, str(lora_path), 1.0, 1.0)
        assert clip_out is clip
        import jax

        base = np.concatenate(
            [np.ravel(v) for v in jax.tree.leaves(model.params)]
        )
        new = np.concatenate(
            [np.ravel(v) for v in jax.tree.leaves(patched.params)]
        )
        assert base.shape == new.shape and not np.allclose(base, new)

        # Zero strength bakes nothing.
        zero, _ = node.load_lora(model, clip, str(lora_path), 0.0, 1.0)
        znew = np.concatenate(
            [np.ravel(v) for v in jax.tree.leaves(zero.params)]
        )
        np.testing.assert_allclose(znew, base, rtol=1e-6, atol=1e-6)

        # Stacking: chained LoraLoaders compose — two strength-1 bakes of the
        # same LoRA equal one strength-2 bake (deltas are linear in strength).
        stacked, _ = node.load_lora(patched, clip, str(lora_path), 1.0, 1.0)
        snew = np.concatenate(
            [np.ravel(v) for v in jax.tree.leaves(stacked.params)]
        )
        assert not np.allclose(snew, new)
        twice, _ = node.load_lora(model, clip, str(lora_path), 2.0, 1.0)
        tnew = np.concatenate(
            [np.ravel(v) for v in jax.tree.leaves(twice.params)]
        )
        np.testing.assert_allclose(snew, tnew, rtol=1e-4, atol=1e-5)

        # Untagged models and missing files fail with instructions
        # (an absent LoRA must never silently return an unpatched model).
        with pytest.raises(ValueError, match="CheckpointLoaderSimple"):
            node.load_lora(object(), clip, str(lora_path), 1.0, 1.0)
        with pytest.raises(ValueError, match="not found"):
            node.load_lora(model, clip, "", 1.0, 1.0)
        with pytest.raises(ValueError, match="not found"):
            node.load_lora(model, clip, "ghost.safetensors", 1.0, 1.0)

    def test_lora_loader_strength_clip_bakes_text_tower(self, tmp_path,
                                                        monkeypatch):
        # A LoRA with kohya lora_te_* keys must rebuild the CLIP wire with the
        # deltas baked into the bundled tower (the
        # strength_clip divergence closed).
        from safetensors.numpy import save_file

        from comfyui_parallelanything_tpu.models import load_safetensors
        from comfyui_parallelanything_tpu.nodes import NODE_CLASS_MAPPINGS

        paths = _synthetic_stock_env(tmp_path, monkeypatch)
        model, clip, _ = (
            NODE_CLASS_MAPPINGS["CheckpointLoaderSimple"]().load(paths["ckpt"])
        )
        sd = load_safetensors(paths["ckpt"])
        target = next(
            k for k in sd
            if k.startswith("cond_stage_model.") and
            k.endswith("self_attn.q_proj.weight")
        )
        out_d, in_d = sd[target].shape
        base_name = (
            target.removeprefix("cond_stage_model.transformer.")
            .removesuffix(".weight").replace(".", "_")
        )
        rng = np.random.default_rng(9)
        lora_path = tmp_path / "te.safetensors"
        save_file({
            f"lora_te_{base_name}.lora_down.weight":
                rng.standard_normal((2, in_d)).astype(np.float32),
            f"lora_te_{base_name}.lora_up.weight":
                rng.standard_normal((out_d, 2)).astype(np.float32),
        }, str(lora_path))

        node = NODE_CLASS_MAPPINGS["LoraLoader"]()
        import jax

        def flat(wire):
            return np.concatenate([
                np.ravel(np.asarray(v, np.float32))
                for v in jax.tree.leaves(wire["encoder"].params)
            ])

        _, clip_out = node.load_lora(model, clip, str(lora_path), 1.0, 1.0)
        assert clip_out is not clip
        assert not np.allclose(flat(clip_out), flat(clip))
        # strength_clip=0 leaves the wire untouched (identity, no rebuild).
        _, clip_zero = node.load_lora(model, clip, str(lora_path), 1.0, 0.0)
        assert clip_zero is clip
        # Upstream wire state (CLIPSetLastLayer's tag) survives the rebuild.
        _, clip_keep = node.load_lora(
            model, {**clip, "clip_skip": 2}, str(lora_path), 1.0, 1.0
        )
        assert clip_keep["clip_skip"] == 2
        assert not np.allclose(flat(clip_keep), flat(clip))
        # A CLIP wire NOT from this checkpoint's bundled towers (no
        # source_ckpt tag — e.g. DualCLIPLoader) is never clobbered by the
        # rebuild; te deltas are skipped with a warning instead.
        external = {k: v for k, v in clip.items() if k != "source_ckpt"}
        _, clip_ext = node.load_lora(model, external, str(lora_path), 1.0, 1.0)
        assert clip_ext is external

    def test_lora_loader_attaches_serving_delegate(self, tmp_path,
                                                    monkeypatch):
        # Round 16 (universal lane batching): a clean 2-D LoRA bake carries a
        # serving delegate — (unpatched base, extracted factors) — so the
        # sampler can submit LoRA traffic as per-lane state of the BASE
        # model's bucket. The delegate's eager merge must reproduce the bake.
        import jax
        from safetensors.numpy import save_file

        from comfyui_parallelanything_tpu.models import load_safetensors
        from comfyui_parallelanything_tpu.models.lora import merge_lora_params
        from comfyui_parallelanything_tpu.nodes import NODE_CLASS_MAPPINGS
        from comfyui_parallelanything_tpu.nodes import _split_lora_delegate

        paths = _synthetic_stock_env(tmp_path, monkeypatch)
        model, clip, _ = (
            NODE_CLASS_MAPPINGS["CheckpointLoaderSimple"]().load(paths["ckpt"])
        )
        sd = load_safetensors(paths["ckpt"])
        target = next(
            k for k in sd
            if k.endswith("attn1.to_q.weight") and "input_blocks" in k
        ).removeprefix("model.diffusion_model.")
        out_d, in_d = sd[f"model.diffusion_model.{target}"].shape
        rng = np.random.default_rng(5)
        lora_path = tmp_path / "style.safetensors"
        save_file({
            f"{target.removesuffix('.weight')}.lora_down.weight":
                rng.standard_normal((2, in_d)).astype(np.float32),
            f"{target.removesuffix('.weight')}.lora_up.weight":
                rng.standard_normal((out_d, 2)).astype(np.float32),
        }, str(lora_path))

        node = NODE_CLASS_MAPPINGS["LoraLoader"]()
        patched, _ = node.load_lora(model, clip, str(lora_path), 1.0, 1.0)
        delegate = patched.lora_delegate
        assert delegate is not None
        assert delegate["base"] is model  # bucket identity == plain traffic
        # Factor merge on the base == the bake (this env's XLA CPU matmuls
        # run at bf16 scale — CLAUDE.md tolerance discipline).
        merged = merge_lora_params(model.params, delegate["factors"])
        for a, b in zip(jax.tree.leaves(merged),
                        jax.tree.leaves(patched.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=1e-4)
        # Chained links accumulate into ONE delegate against the same base.
        stacked, _ = node.load_lora(patched, clip, str(lora_path), 1.0, 1.0)
        assert stacked.lora_delegate["base"] is model
        merged2 = merge_lora_params(model.params,
                                    stacked.lora_delegate["factors"])
        for a, b in zip(jax.tree.leaves(merged2),
                        jax.tree.leaves(stacked.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=1e-4)

        # The sampler split: plain positive engages the delegate; inpaint
        # state (which the factor recompose can't thread) keeps the bake.
        got_model, got_lora = _split_lora_delegate(patched, {})
        assert got_model is model and got_lora is delegate["factors"]
        keep_model, keep_lora = _split_lora_delegate(
            patched, {"inpaint": {"mask": None, "masked_latent": None}}
        )
        assert keep_model is patched and keep_lora is None

        # A pair the bake itself skips (no UNet match) doesn't block the
        # delegate: factorization works off the WEIGHT DELTA, so whatever
        # the bake applied is exactly what the factors carry.
        ghost_path = tmp_path / "ghost.safetensors"
        save_file({
            f"{target.removesuffix('.weight')}.lora_down.weight":
                rng.standard_normal((2, in_d)).astype(np.float32),
            f"{target.removesuffix('.weight')}.lora_up.weight":
                rng.standard_normal((out_d, 2)).astype(np.float32),
            "ghost_block.lora_down.weight":
                rng.standard_normal((2, 8)).astype(np.float32),
            "ghost_block.lora_up.weight":
                rng.standard_normal((8, 2)).astype(np.float32),
        }, str(ghost_path))
        ghosted, _ = node.load_lora(model, clip, str(ghost_path), 1.0, 1.0)
        assert ghosted.lora_delegate is not None
        merged3 = merge_lora_params(model.params,
                                    ghosted.lora_delegate["factors"])
        for a, b in zip(jax.tree.leaves(merged3),
                        jax.tree.leaves(ghosted.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=1e-4)

    def test_save_image_defaults_to_pa_output_dir(self, tmp_path, monkeypatch):
        # Stock exports carry only filename_prefix; images must land in the
        # host-configured root (the one the API server serves /view from).
        from comfyui_parallelanything_tpu.nodes import NODE_CLASS_MAPPINGS

        monkeypatch.setenv("PA_OUTPUT_DIR", str(tmp_path / "served"))
        node = NODE_CLASS_MAPPINGS["SaveImage"]()
        (paths,) = node.run(
            images=np.zeros((1, 8, 8, 3), np.float32), filename_prefix="x"
        )
        assert all(p.startswith(str(tmp_path / "served")) for p in paths)


class TestKSamplerAdvanced:
    """Stock KSamplerAdvanced semantics: step-window runs, leftover noise,
    add_noise-disabled continuation (the SDXL base→refiner template driver)."""

    def _toy(self):
        # Deterministic eps-style toy model (no params): enough for exact
        # split-vs-full trajectory equality under euler.
        return lambda x, t, context=None, **kw: x * 0.05

    def _conds(self):
        import jax.numpy as jnp

        return ({"context": jnp.zeros((1, 3, 5))},
                {"context": jnp.zeros((1, 3, 5))})

    def test_split_run_matches_full_window(self):
        import jax.numpy as jnp

        from comfyui_parallelanything_tpu.nodes import TPUKSamplerAdvanced

        pos, neg = self._conds()
        lat = {"samples": jnp.zeros((1, 8, 8, 4))}
        node = TPUKSamplerAdvanced()
        kw = dict(noise_seed=3, steps=4, cfg=1.0, sampler_name="euler",
                  scheduler="normal", positive=pos, negative=neg)
        (full,) = node.sample(
            self._toy(), add_noise="enable", latent_image=lat,
            start_at_step=0, end_at_step=10000,
            return_with_leftover_noise="disable", **kw,
        )
        (base,) = node.sample(
            self._toy(), add_noise="enable", latent_image=lat,
            start_at_step=0, end_at_step=2,
            return_with_leftover_noise="enable", **kw,
        )
        (cont,) = node.sample(
            self._toy(), add_noise="disable", latent_image=base,
            start_at_step=2, end_at_step=10000,
            return_with_leftover_noise="disable", **kw,
        )
        np.testing.assert_allclose(
            np.asarray(cont["samples"]), np.asarray(full["samples"]),
            rtol=1e-5, atol=1e-6,
        )
        # The base half still carries noise (sigma[2] > 0): it must differ
        # from the fully-denoised run.
        assert not np.allclose(
            np.asarray(base["samples"]), np.asarray(full["samples"])
        )

    def test_force_full_denoise_on_short_window(self):
        import jax.numpy as jnp

        from comfyui_parallelanything_tpu.nodes import TPUKSamplerAdvanced

        pos, neg = self._conds()
        lat = {"samples": jnp.zeros((1, 8, 8, 4))}
        node = TPUKSamplerAdvanced()
        kw = dict(noise_seed=3, steps=4, cfg=1.0, sampler_name="euler",
                  scheduler="normal", positive=pos, negative=neg,
                  add_noise="enable", latent_image=lat, start_at_step=0,
                  end_at_step=2)
        (leftover,) = node.sample(
            self._toy(), return_with_leftover_noise="enable", **kw
        )
        (forced,) = node.sample(
            self._toy(), return_with_leftover_noise="disable", **kw
        )
        assert not np.allclose(
            np.asarray(leftover["samples"]), np.asarray(forced["samples"])
        )

    def test_empty_window_returns_latent(self):
        import jax.numpy as jnp

        from comfyui_parallelanything_tpu.nodes import TPUKSamplerAdvanced

        pos, neg = self._conds()
        lat = {"samples": jnp.ones((1, 8, 8, 4))}
        (out,) = TPUKSamplerAdvanced().sample(
            self._toy(), add_noise="enable", noise_seed=0, steps=4, cfg=1.0,
            sampler_name="euler", scheduler="normal", positive=pos,
            negative=neg, latent_image=lat, start_at_step=3, end_at_step=3,
            return_with_leftover_noise="disable",
        )
        np.testing.assert_array_equal(
            np.asarray(out["samples"]), np.asarray(lat["samples"])
        )

    def test_base_refiner_template_runs_unchanged(self, tmp_path, monkeypatch):
        """The stock SDXL base→refiner API export shape — two checkpoint
        loaders, four text encodes, chained KSamplerAdvanced — runs as-is
        (the tiny sd15 synthetic checkpoint stands in for both stages; the
        node surface and window semantics are family-independent)."""
        paths = _synthetic_stock_env(tmp_path, monkeypatch)
        monkeypatch.setenv("PA_OUTPUT_DIR", str(tmp_path / "out"))
        wf = {
            "4": {"class_type": "CheckpointLoaderSimple",
                  "inputs": {"ckpt_name": paths["ckpt"]}},
            "12": {"class_type": "CheckpointLoaderSimple",
                   "inputs": {"ckpt_name": paths["ckpt"]}},
            "5": {"class_type": "EmptyLatentImage",
                  "inputs": {"width": 32, "height": 32, "batch_size": 1}},
            "6": {"class_type": "CLIPTextEncode",
                  "inputs": {"text": "a watercolor lighthouse", "clip": ["4", 1]}},
            "7": {"class_type": "CLIPTextEncode",
                  "inputs": {"text": "blurry", "clip": ["4", 1]}},
            "15": {"class_type": "CLIPTextEncode",
                   "inputs": {"text": "a watercolor lighthouse",
                              "clip": ["12", 1]}},
            "16": {"class_type": "CLIPTextEncode",
                   "inputs": {"text": "blurry", "clip": ["12", 1]}},
            "10": {"class_type": "KSamplerAdvanced",
                   "inputs": {"add_noise": "enable", "noise_seed": 721897,
                              "steps": 4, "cfg": 2.0,
                              "sampler_name": "euler", "scheduler": "normal",
                              "start_at_step": 0, "end_at_step": 2,
                              "return_with_leftover_noise": "enable",
                              "model": ["4", 0], "positive": ["6", 0],
                              "negative": ["7", 0], "latent_image": ["5", 0]}},
            "11": {"class_type": "KSamplerAdvanced",
                   "inputs": {"add_noise": "disable", "noise_seed": 0,
                              "steps": 4, "cfg": 2.0,
                              "sampler_name": "euler", "scheduler": "normal",
                              "start_at_step": 2, "end_at_step": 10000,
                              "return_with_leftover_noise": "disable",
                              "model": ["12", 0], "positive": ["15", 0],
                              "negative": ["16", 0],
                              "latent_image": ["10", 0]}},
            "8": {"class_type": "VAEDecode",
                  "inputs": {"samples": ["11", 0], "vae": ["12", 2]}},
            "9": {"class_type": "SaveImage",
                  "inputs": {"images": ["8", 0], "filename_prefix": "refined",
                             "output_dir": str(tmp_path / "out")}},
        }
        out = run_workflow(wf)
        assert np.isfinite(np.asarray(out["8"][0])).all()
        assert all(os.path.exists(p) for p in out["9"][0])


class TestNewStockLoaders:
    def test_unet_loader_bare_diffusion_file(self, tmp_path, monkeypatch):
        from safetensors.numpy import save_file

        from comfyui_parallelanything_tpu.models import load_safetensors
        from comfyui_parallelanything_tpu.nodes_compat import UNETLoader

        paths = _synthetic_stock_env(tmp_path, monkeypatch)
        sd = load_safetensors(paths["ckpt"])
        bare = {
            k.removeprefix("model.diffusion_model."): np.ascontiguousarray(v)
            for k, v in sd.items()
            if k.startswith("model.diffusion_model.")
        }
        unet_path = tmp_path / "unet_only.safetensors"
        save_file(bare, str(unet_path))
        (model,) = UNETLoader().load_unet(str(unet_path))
        assert model.source["family"] == "sd15"
        assert hasattr(model, "apply") and hasattr(model, "params")

    def test_lora_loader_model_only(self, tmp_path, monkeypatch):
        import jax
        from safetensors.numpy import save_file

        from comfyui_parallelanything_tpu.models import load_safetensors
        from comfyui_parallelanything_tpu.nodes import NODE_CLASS_MAPPINGS

        paths = _synthetic_stock_env(tmp_path, monkeypatch)
        model, clip, _ = (
            NODE_CLASS_MAPPINGS["CheckpointLoaderSimple"]().load(paths["ckpt"])
        )
        sd = load_safetensors(paths["ckpt"])
        target = next(
            k for k in sd
            if k.endswith("attn1.to_q.weight") and "input_blocks" in k
        ).removeprefix("model.diffusion_model.")
        out_d, in_d = sd[f"model.diffusion_model.{target}"].shape
        rng = np.random.default_rng(6)
        lora_path = tmp_path / "style.safetensors"
        save_file({
            f"{target.removesuffix('.weight')}.lora_down.weight":
                rng.standard_normal((2, in_d)).astype(np.float32),
            f"{target.removesuffix('.weight')}.lora_up.weight":
                rng.standard_normal((out_d, 2)).astype(np.float32),
        }, str(lora_path))
        node = NODE_CLASS_MAPPINGS["LoraLoaderModelOnly"]()
        (patched,) = node.load_lora_model_only(model, str(lora_path), 1.0)

        def flat(m):
            return np.concatenate(
                [np.ravel(v) for v in jax.tree.leaves(m.params)]
            )

        assert not np.allclose(flat(patched), flat(model))

    def test_vae_loader_image_layout(self, tmp_path, monkeypatch):
        import jax
        import jax.numpy as jnp
        from safetensors.numpy import save_file

        from comfyui_parallelanything_tpu.nodes_compat import VAELoader
        from tests.test_vae import TINY as TINY_VAE, _ldm_layout_sd
        from comfyui_parallelanything_tpu.models import build_vae

        vae = build_vae(TINY_VAE, jax.random.key(1), sample_hw=16)
        vae_path = tmp_path / "ext_vae.safetensors"
        save_file(
            {k: np.ascontiguousarray(v)
             for k, v in _ldm_layout_sd(TINY_VAE, vae.params).items()},
            str(vae_path),
        )
        # The tiny config must be what sniffing resolves: pin it.
        import comfyui_parallelanything_tpu.models as models_pkg

        monkeypatch.setattr(models_pkg, "sd_vae_config", lambda: TINY_VAE)
        import comfyui_parallelanything_tpu.models.loader as loader_mod

        monkeypatch.setattr(
            loader_mod, "sniff_vae_config", lambda sd: TINY_VAE
        )
        (loaded,) = VAELoader().load(str(vae_path))
        z = loaded.encode(jnp.zeros((1, 16, 16, 3)), None)
        assert z.shape[-1] == TINY_VAE.z_channels

    def test_vae_loader_routes_wan_video_layout(self, tmp_path, monkeypatch):
        from safetensors.numpy import save_file

        from comfyui_parallelanything_tpu.nodes_compat import VAELoader
        import comfyui_parallelanything_tpu.models.loader as loader_mod

        path = tmp_path / "wan_vae.safetensors"
        save_file(
            {"decoder.upsamples.0.residual.0.gamma":
                 np.zeros((4, 1, 1, 1), np.float32)},
            str(path),
        )
        seen = {}

        def fake_load(p, cfg=None):
            seen["path"] = p
            return "video-vae"

        monkeypatch.setattr(loader_mod, "load_wan_vae_checkpoint", fake_load)
        (out,) = VAELoader().load(str(path))
        assert out == "video-vae" and seen["path"] == str(path)

    def test_vae_loader_missing_file(self):
        from comfyui_parallelanything_tpu.nodes_compat import VAELoader

        with pytest.raises(ValueError, match="not found"):
            VAELoader().load("ghost_vae.safetensors")

    def test_clip_loader_single_tower(self, tmp_path, monkeypatch):
        from safetensors.numpy import save_file

        from comfyui_parallelanything_tpu.nodes_compat import CLIPLoader
        import comfyui_parallelanything_tpu.models.text_encoders as te_mod
        from tests.test_text_encoders import TINY_CLIP, _hf_clip

        _synthetic_stock_env(tmp_path, monkeypatch)  # tokenizer env
        monkeypatch.setattr(te_mod, "clip_l_config", lambda: TINY_CLIP)
        hf = _hf_clip(TINY_CLIP, "quick_gelu")
        enc_path = tmp_path / "clip_l.safetensors"
        save_file(
            {k: np.ascontiguousarray(v.detach().numpy())
             for k, v in hf.state_dict().items()},
            str(enc_path),
        )
        (wire,) = CLIPLoader().load(str(enc_path), type="stable_diffusion")
        assert wire["encoder"] is not None and wire["tokenizer"] is not None

    def test_clip_loader_wan_needs_t5_tokenizer(self, monkeypatch):
        from comfyui_parallelanything_tpu.nodes_compat import CLIPLoader

        monkeypatch.delenv("PA_T5_TOKENIZER_JSON", raising=False)
        with pytest.raises(ValueError, match="PA_T5_TOKENIZER_JSON"):
            CLIPLoader().load("umt5_xxl.safetensors", type="wan")


class TestUnclip:
    def test_sniff_sd21_unclip(self):
        sd = {
            "input_blocks.0.0.weight": np.zeros((1, 4)),
            "label_emb.0.0.weight": np.zeros((1024, 2048)),
            "input_blocks.1.1.transformer_blocks.0.attn2.to_k.weight":
                np.zeros((320, 1024)),
        }
        assert sniff_model_family(sd) == "sd21-unclip"
        # SDXL keeps sniffing sdxl (no transformer at input_blocks.1).
        sdxl = {"input_blocks.0.0.weight": np.zeros((1, 4)),
                "label_emb.0.0.weight": np.zeros((1, 2816))}
        assert sniff_model_family(sdxl) == "sdxl"

    def test_unclip_adm_vector(self):
        from comfyui_parallelanything_tpu.models.unet import unclip_adm

        tags = [{"embeds": np.ones((1, 24), np.float32), "strength": 1.0,
                 "noise_augmentation": 0.0}]
        y = unclip_adm(tags, 32)
        assert y.shape == (1, 32)
        # Zero augmentation at level 0 still q_samples with sqrt(acp[0])~1:
        # the embed half stays close to the input, the level half is the
        # sinusoidal embedding of 0.
        assert np.allclose(np.asarray(y[:, :24]), 1.0, atol=0.05)
        # Strength scales the whole vector.
        y2 = unclip_adm([{**tags[0], "strength": 2.0}], 32)
        np.testing.assert_allclose(
            np.asarray(y2), 2 * np.asarray(y), rtol=1e-5
        )
        # Multiple tags merge (re-augmented sum) without shape drift.
        y3 = unclip_adm(tags + [{**tags[0], "noise_augmentation": 0.5}], 32)
        assert y3.shape == (1, 32) and np.isfinite(np.asarray(y3)).all()

    def test_unclip_conditioning_node_tags_and_samples(self):
        import jax
        import jax.numpy as jnp

        from comfyui_parallelanything_tpu.models import build_unet, sd15_config
        from comfyui_parallelanything_tpu.nodes import TPUKSampler
        from comfyui_parallelanything_tpu.nodes_compat import unCLIPConditioning

        cfg = sd15_config(
            model_channels=32, channel_mult=(1, 2), transformer_depth=(1, 1),
            attention_levels=(0, 1), context_dim=16, num_heads=4,
            norm_groups=8, adm_in_channels=32, prediction="v",
            dtype=jnp.float32,
        )
        model = build_unet(cfg, jax.random.key(0), sample_shape=(1, 8, 8, 4))
        cvo = {"image_embeds": jnp.ones((1, 24)), "last_hidden": None,
               "penultimate": None}
        pos = {"context": jnp.zeros((1, 3, 16))}
        (tagged,) = unCLIPConditioning().apply_adm(pos, cvo, 1.0, 0.2)
        assert len(tagged["unclip"]) == 1
        # Chaining stacks.
        (tagged2,) = unCLIPConditioning().apply_adm(tagged, cvo, 0.5, 0.0)
        assert len(tagged2["unclip"]) == 2
        neg = {"context": jnp.zeros((1, 3, 16))}
        (out,) = TPUKSampler().sample(
            model, tagged, {"samples": jnp.zeros((2, 8, 8, 4))}, seed=1,
            steps=2, cfg=3.0, sampler_name="euler", scheduler="normal",
            negative=neg,
        )
        assert out["samples"].shape == (2, 8, 8, 4)
        assert np.isfinite(np.asarray(out["samples"])).all()


def _synthetic_wan_env(tmp_path, monkeypatch):
    """Tiny WAN i2v world for the stock template: bare DiT file (official
    Wan2.x layout incl. the img_emb CLIP branch), official-layout video VAE,
    UMT5 encoder + tokenizer.json, HF-layout CLIP-vision tower, start image —
    all wired through the same env vars / preset monkeypatches the shims read."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from PIL import Image
    from safetensors.numpy import save_file

    import comfyui_parallelanything_tpu.models as models_pkg
    import comfyui_parallelanything_tpu.models.video_vae as vv_mod
    from comfyui_parallelanything_tpu.models.wan import WanConfig, build_wan
    from tests.test_convert_wan import _official_layout_sd
    from tests.test_golden_video_vae import CFG as VCFG, TWanVAE
    from tests.test_text_encoders import TINY_T5
    from tests.test_vision import TINY as TINY_VIS, _hf_vision

    import torch

    # -- WAN i2v DiT (official layout, CLIP branch) -------------------------
    zc = VCFG.z_channels
    wcfg = WanConfig(
        in_channels=2 * zc + 4, out_channels=zc, hidden_size=48, ffn_dim=96,
        num_heads=4, depth=2, text_dim=TINY_T5.d_model, freq_dim=16,
        img_dim=TINY_VIS.hidden_size, dtype=jnp.float32,
    )
    dit = build_wan(
        wcfg, jax.random.key(0), sample_shape=(1, 2, 4, 4, 2 * zc + 4),
        txt_len=6,
    )
    dit_path = tmp_path / "wan_i2v_tiny.safetensors"
    save_file(
        {k: np.ascontiguousarray(v)
         for k, v in _official_layout_sd(wcfg, dit.params).items()},
        str(dit_path),
    )
    # The loader's family preset; in_channels/img_dim re-sniff off the file.
    base_cfg = dataclasses.replace(wcfg, in_channels=zc, img_dim=None)
    monkeypatch.setattr(models_pkg, "wan_1_3b_config", lambda: base_cfg)

    # -- WAN t2v DiT (bare-latent input, no CLIP branch) --------------------
    dit_t2v = build_wan(
        base_cfg, jax.random.key(7), sample_shape=(1, 2, 4, 4, zc), txt_len=6
    )
    t2v_path = tmp_path / "wan_t2v_tiny.safetensors"
    save_file(
        {k: np.ascontiguousarray(v)
         for k, v in _official_layout_sd(base_cfg, dit_t2v.params).items()},
        str(t2v_path),
    )

    # -- video VAE (official torch layout) ----------------------------------
    torch.manual_seed(11)
    tvae = TWanVAE(VCFG).eval()
    vae_path = tmp_path / "wan_vae_tiny.safetensors"
    save_file(
        {k: np.ascontiguousarray(v.detach().numpy())
         for k, v in tvae.state_dict().items()},
        str(vae_path),
    )
    monkeypatch.setattr(vv_mod, "wan_vae_config", lambda: VCFG)

    # -- UMT5 text encoder + tokenizer --------------------------------------
    import transformers

    t5_cfg = dataclasses.replace(TINY_T5, per_layer_bias=True)
    hf_cfg = transformers.UMT5Config(
        vocab_size=t5_cfg.vocab_size, d_model=t5_cfg.d_model,
        d_kv=t5_cfg.d_kv, d_ff=t5_cfg.d_ff, num_layers=t5_cfg.num_layers,
        num_heads=t5_cfg.num_heads,
        relative_attention_num_buckets=t5_cfg.relative_buckets,
        relative_attention_max_distance=t5_cfg.relative_max_distance,
        feed_forward_proj="gated-gelu", dropout_rate=0.0,
    )
    torch.manual_seed(1)
    hf_t5 = transformers.UMT5EncoderModel(hf_cfg).eval()
    umt5_path = tmp_path / "umt5_tiny.safetensors"
    save_file(
        {k: np.ascontiguousarray(v.detach().numpy())
         for k, v in hf_t5.state_dict().items()},
        str(umt5_path),
    )
    monkeypatch.setattr(models_pkg, "umt5_xxl_config", lambda: t5_cfg)

    tokenizers = pytest.importorskip("tokenizers")
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace

    vocab = {"[UNK]": 0, "</s>": 1, "a": 5, "cat": 6, "walking": 7,
             "blurry": 8}
    t = tokenizers.Tokenizer(WordLevel(vocab, unk_token="[UNK]"))
    t.pre_tokenizer = Whitespace()
    tok_path = tmp_path / "t5_tokenizer.json"
    t.save(str(tok_path))
    monkeypatch.setenv("PA_T5_TOKENIZER_JSON", str(tok_path))

    # -- CLIP vision tower (HF layout) --------------------------------------
    vis_path = tmp_path / "clip_vision_tiny.safetensors"
    hf_vis = _hf_vision(TINY_VIS, "quick_gelu")
    save_file(
        {k: np.ascontiguousarray(v.detach().numpy())
         for k, v in hf_vis.state_dict().items()},
        str(vis_path),
    )

    # -- start image ---------------------------------------------------------
    img_path = tmp_path / "start.png"
    Image.fromarray(
        (np.full((16, 16, 3), 0.5) * 255).astype(np.uint8)
    ).save(str(img_path))
    monkeypatch.setenv("PA_INPUT_DIR", str(tmp_path))

    return {
        "dit": str(dit_path), "dit_t2v": str(t2v_path),
        "vae": str(vae_path), "umt5": str(umt5_path),
        "vision": str(vis_path), "image": "start.png",
    }


class TestStockWanI2VWorkflow:
    def test_wan_i2v_template_runs_unchanged(self, tmp_path, monkeypatch):
        """The stock WAN image-to-video API export shape — UNETLoader +
        CLIPLoader(wan) + VAELoader + CLIPVisionLoader/Encode +
        WanImageToVideo + KSampler + VAEDecode + SaveAnimatedWEBP — runs
        as-is on the tiny synthetic WAN i2v world."""
        paths = _synthetic_wan_env(tmp_path, monkeypatch)
        monkeypatch.setenv("PA_OUTPUT_DIR", str(tmp_path / "out"))
        wf = {
            "37": {"class_type": "UNETLoader",
                   "inputs": {"unet_name": paths["dit"],
                              "weight_dtype": "default"}},
            "38": {"class_type": "CLIPLoader",
                   "inputs": {"clip_name": paths["umt5"], "type": "wan"}},
            "39": {"class_type": "VAELoader",
                   "inputs": {"vae_name": paths["vae"]}},
            "49": {"class_type": "CLIPVisionLoader",
                   "inputs": {"clip_name": paths["vision"]}},
            "52": {"class_type": "LoadImage",
                   "inputs": {"image": paths["image"]}},
            "51": {"class_type": "CLIPVisionEncode",
                   "inputs": {"clip_vision": ["49", 0], "image": ["52", 0],
                              "crop": "none"}},
            "6": {"class_type": "CLIPTextEncode",
                  "inputs": {"text": "a cat walking", "clip": ["38", 0]}},
            "7": {"class_type": "CLIPTextEncode",
                  "inputs": {"text": "blurry", "clip": ["38", 0]}},
            "50": {"class_type": "WanImageToVideo",
                   "inputs": {"positive": ["6", 0], "negative": ["7", 0],
                              "vae": ["39", 0], "width": 16, "height": 16,
                              "length": 5, "batch_size": 1,
                              "clip_vision_output": ["51", 0],
                              "start_image": ["52", 0]}},
            "3": {"class_type": "KSampler",
                  "inputs": {"seed": 7, "steps": 2, "cfg": 1.0,
                             "sampler_name": "euler", "scheduler": "normal",
                             "denoise": 1.0, "model": ["37", 0],
                             "positive": ["50", 0], "negative": ["50", 1],
                             "latent_image": ["50", 2]}},
            "8": {"class_type": "VAEDecode",
                  "inputs": {"samples": ["3", 0], "vae": ["39", 0]}},
            "28": {"class_type": "SaveAnimatedWEBP",
                   "inputs": {"images": ["8", 0], "fps": 8.0,
                              "filename_prefix": "wan_i2v"}},
        }
        out = run_workflow(wf)
        video = np.asarray(out["8"][0])
        assert video.shape == (1, 5, 16, 16, 3) or video.shape == (5, 16, 16, 3)
        assert np.isfinite(video).all()
        assert all(os.path.exists(p) for p in out["28"][0])


class TestUnclipCheckpointLoader:
    def test_unclip_single_file_loads_all_four_wires(self, tmp_path,
                                                     monkeypatch):
        """A synthetic sd21-unclip single file — v-pred UNet with label_emb +
        1024-ctx, OpenCLIP-H text tower, VAE, AND the OpenCLIP-layout ViT
        image encoder under embedder.model.visual.* — loads through
        unCLIPCheckpointLoader into MODEL/CLIP/VAE/CLIP_VISION, and the
        vision wire encodes an image into CLIP_VISION_OUTPUT."""
        import jax
        import jax.numpy as jnp
        from safetensors.numpy import save_file

        import comfyui_parallelanything_tpu.models as models_pkg
        from comfyui_parallelanything_tpu.models import build_unet, build_vae
        from comfyui_parallelanything_tpu.models.text_encoders import (
            build_clip_text,
            open_clip_h_config,
        )
        from comfyui_parallelanything_tpu.models.vision import (
            CLIPVisionConfig,
            build_clip_vision,
        )
        from comfyui_parallelanything_tpu.nodes_compat import (
            CLIPVisionEncode,
            unCLIPCheckpointLoader,
        )
        from tests.test_convert_unet import _ldm_sd
        from tests.test_text_encoders import TestOpenCLIPConversion
        from tests.test_vae import TINY as TINY_VAE, _ldm_layout_sd
        from tests.test_vision import _openclip_visual_sd

        # Text tower must be 1024-wide: the UNet's ctx width IS the sniff key.
        h_cfg = open_clip_h_config(
            vocab_size=100, hidden_size=1024, num_layers=1, num_heads=8,
            max_len=16, intermediate_size=64, projection_dim=32,
            dtype=jnp.float32,
        )
        monkeypatch.setattr(models_pkg, "open_clip_h_config", lambda: h_cfg)
        monkeypatch.setattr(models_pkg, "sd_vae_config", lambda: TINY_VAE)
        real_sd21 = models_pkg.sd21_config

        def tiny_sd21(**kw):
            kw.pop("prediction", None)
            return real_sd21(
                model_channels=32, channel_mult=(1, 2), num_res_blocks=1,
                attention_levels=(0, 1), transformer_depth=(1, 1),
                num_heads=4, context_dim=h_cfg.hidden_size, norm_groups=8,
                prediction="v", dtype=jnp.float32, **kw,
            )

        monkeypatch.setattr(models_pkg, "sd21_config", tiny_sd21)

        ucfg = tiny_sd21(adm_in_channels=48)
        unet = build_unet(ucfg, jax.random.key(0), sample_shape=(1, 8, 8, 4))
        vae = build_vae(TINY_VAE, jax.random.key(1), sample_hw=16)
        te = build_clip_text(h_cfg, rng=jax.random.key(2))
        v_cfg = CLIPVisionConfig(
            image_size=28, patch_size=7, hidden_size=32, num_layers=2,
            num_heads=4, intermediate_size=64, act="gelu",
            projection_dim=24, dtype=jnp.float32,
        )
        venc = build_clip_vision(v_cfg, rng=jax.random.key(3))

        sd = {
            f"model.diffusion_model.{k}": np.ascontiguousarray(v)
            for k, v in _ldm_sd(ucfg, unet.params).items()
        }
        sd.update({
            f"first_stage_model.{k}": np.ascontiguousarray(v)
            for k, v in _ldm_layout_sd(TINY_VAE, vae.params).items()
        })
        sd.update({
            f"cond_stage_model.model.{k}": np.ascontiguousarray(v)
            for k, v in TestOpenCLIPConversion._openclip_layout(
                h_cfg, te.params
            ).items()
        })
        sd.update({
            f"embedder.model.visual.{k}": np.ascontiguousarray(v)
            for k, v in _openclip_visual_sd(v_cfg, venc.params).items()
        })
        ckpt = tmp_path / "unclip.safetensors"
        save_file(sd, str(ckpt))
        _word_level_tokenizer(tmp_path, monkeypatch)

        model, clip, vae_w, clip_vision = (
            unCLIPCheckpointLoader().load(str(ckpt))
        )
        assert model.source["family"] == "sd21-unclip"
        assert model.config.prediction == "v"
        assert model.config.adm_in_channels == 48
        # The vision wire encodes — sniffed heads differ from the tiny
        # tower's (the head table keys real widths), so check shape/finite
        # rather than golden values; real towers sniff exactly.
        img = np.random.default_rng(0).uniform(size=(1, 28, 28, 3)).astype(
            np.float32
        )
        (cvo,) = CLIPVisionEncode().encode(clip_vision, img, crop="center")
        assert cvo["image_embeds"].shape == (1, 24)
        assert np.isfinite(np.asarray(cvo["image_embeds"])).all()
        # Not-an-unclip file raises with guidance.
        plain = {k: v for k, v in sd.items()
                 if not k.startswith("embedder.")}
        ckpt2 = tmp_path / "plain.safetensors"
        save_file(plain, str(ckpt2))
        with pytest.raises(ValueError, match="not an unCLIP"):
            unCLIPCheckpointLoader().load(str(ckpt2))


class TestStockWanT2VWorkflow:
    def test_wan_t2v_template_runs_unchanged(self, tmp_path, monkeypatch):
        """The stock WAN text-to-video API export shape — UNETLoader +
        CLIPLoader(wan) + VAELoader + EmptyHunyuanLatentVideo (the t2v
        latent entry) + KSampler + VAEDecode + SaveAnimatedWEBP — runs
        as-is on the tiny synthetic WAN world."""
        paths = _synthetic_wan_env(tmp_path, monkeypatch)
        monkeypatch.setenv("PA_OUTPUT_DIR", str(tmp_path / "out"))
        wf = {
            "37": {"class_type": "UNETLoader",
                   "inputs": {"unet_name": paths["dit_t2v"],
                              "weight_dtype": "default"}},
            "38": {"class_type": "CLIPLoader",
                   "inputs": {"clip_name": paths["umt5"], "type": "wan"}},
            "39": {"class_type": "VAELoader",
                   "inputs": {"vae_name": paths["vae"]}},
            "6": {"class_type": "CLIPTextEncode",
                  "inputs": {"text": "a cat walking", "clip": ["38", 0]}},
            "7": {"class_type": "CLIPTextEncode",
                  "inputs": {"text": "blurry", "clip": ["38", 0]}},
            "40": {"class_type": "EmptyHunyuanLatentVideo",
                   "inputs": {"width": 16, "height": 16, "length": 5,
                              "batch_size": 1}},
            "3": {"class_type": "KSampler",
                  "inputs": {"seed": 3, "steps": 2, "cfg": 1.0,
                             "sampler_name": "euler", "scheduler": "normal",
                             "denoise": 1.0, "model": ["37", 0],
                             "positive": ["6", 0], "negative": ["7", 0],
                             "latent_image": ["40", 0]}},
            "8": {"class_type": "VAEDecode",
                  "inputs": {"samples": ["3", 0], "vae": ["39", 0]}},
            "28": {"class_type": "SaveAnimatedWEBP",
                   "inputs": {"images": ["8", 0], "fps": 8.0,
                              "filename_prefix": "wan_t2v"}},
        }
        out = run_workflow(wf)
        video = np.asarray(out["8"][0])
        assert video.shape[-1] == 3 and np.isfinite(video).all()
        assert all(os.path.exists(p) for p in out["28"][0])


class TestUnclipReviewFixes:
    def _adm_model(self):
        import jax
        import jax.numpy as jnp

        from comfyui_parallelanything_tpu.models import build_unet, sd15_config

        cfg = sd15_config(
            model_channels=32, channel_mult=(1, 2), transformer_depth=(1, 1),
            attention_levels=(0, 1), context_dim=16, num_heads=4,
            norm_groups=8, adm_in_channels=32, prediction="v",
            dtype=jnp.float32,
        )
        return build_unet(cfg, jax.random.key(0), sample_shape=(1, 8, 8, 4))

    def test_untagged_adm_model_samples_with_zero_adm(self):
        # A plain txt2img graph on an adm checkpoint (no unCLIPConditioning,
        # no pooled) must sample against a zeros adm vector like stock, not
        # crash on a missing/mis-sized y.
        import jax.numpy as jnp

        from comfyui_parallelanything_tpu.nodes import TPUKSampler

        model = self._adm_model()
        (out,) = TPUKSampler().sample(
            model, {"context": jnp.zeros((1, 3, 16))},
            {"samples": jnp.zeros((1, 8, 8, 4))}, seed=0, steps=2, cfg=3.0,
            sampler_name="euler", scheduler="normal",
            negative={"context": jnp.zeros((1, 3, 16))},
        )
        assert np.isfinite(np.asarray(out["samples"])).all()

    def test_wrong_width_text_pooled_dropped_for_unclip_context(self):
        # context_dim 1024 marks the sd21-unclip family: the text tower's
        # pooled never feeds the adm head (stock drops it); tiny config here
        # has context 16, so emulate by patching the gate's width read.
        import jax.numpy as jnp

        from comfyui_parallelanything_tpu.nodes import TPUKSampler

        model = self._adm_model()
        # Non-1024 context + wrong-width pooled → diagnosable error.
        with pytest.raises(ValueError, match="adm head expects"):
            TPUKSampler().sample(
                model,
                {"context": jnp.zeros((1, 3, 16)),
                 "pooled": jnp.zeros((1, 24))},
                {"samples": jnp.zeros((1, 8, 8, 4))}, seed=0, steps=1,
                cfg=1.0, sampler_name="euler", scheduler="normal",
            )

    def test_unclip_adm_uses_cosine_alpha_bar(self):
        # squaredcos_cap_v2, not the linear table: at level 500 the cosine
        # alpha-bar keeps ~0.49 of the signal (linear keeps ~0.08).
        from comfyui_parallelanything_tpu.models.unet import unclip_adm

        tags = [{"embeds": np.ones((1, 24), np.float32),
                 "noise_augmentation": 0.5}]
        y = np.asarray(unclip_adm(tags, 32))
        signal = float(np.mean(y[:, :24]))
        # sqrt(acp_cos[500]) ~ 0.70 of the unit embed; linear would be ~0.28.
        assert 0.5 < signal < 0.9, signal


class TestCLIPLoaderTokenBudget:
    def test_wan_t5_max_len_512(self, tmp_path, monkeypatch):
        import dataclasses

        import torch
        import transformers
        from safetensors.numpy import save_file

        import comfyui_parallelanything_tpu.models as models_pkg
        from comfyui_parallelanything_tpu.nodes_compat import CLIPLoader
        from tests.test_text_encoders import TINY_T5

        t5_cfg = dataclasses.replace(TINY_T5, per_layer_bias=True)
        hf_cfg = transformers.UMT5Config(
            vocab_size=t5_cfg.vocab_size, d_model=t5_cfg.d_model,
            d_kv=t5_cfg.d_kv, d_ff=t5_cfg.d_ff, num_layers=t5_cfg.num_layers,
            num_heads=t5_cfg.num_heads,
            relative_attention_num_buckets=t5_cfg.relative_buckets,
            relative_attention_max_distance=t5_cfg.relative_max_distance,
            feed_forward_proj="gated-gelu", dropout_rate=0.0,
        )
        torch.manual_seed(0)
        hf = transformers.UMT5EncoderModel(hf_cfg).eval()
        path = tmp_path / "umt5_tiny.safetensors"
        save_file({k: np.ascontiguousarray(v.detach().numpy())
                   for k, v in hf.state_dict().items()}, str(path))
        monkeypatch.setattr(models_pkg, "umt5_xxl_config", lambda: t5_cfg)

        tokenizers = pytest.importorskip("tokenizers")
        from tokenizers.models import WordLevel
        from tokenizers.pre_tokenizers import Whitespace

        t = tokenizers.Tokenizer(
            WordLevel({"[UNK]": 0, "</s>": 1, "a": 5}, unk_token="[UNK]")
        )
        t.pre_tokenizer = Whitespace()
        tok = tmp_path / "t5_tok.json"
        t.save(str(tok))
        monkeypatch.setenv("PA_T5_TOKENIZER_JSON", str(tok))
        (wire,) = CLIPLoader().load(str(path), type="wan")
        # WAN prompts tokenize at 512, not the CLIP default 77 (stock umt5
        # budget) — a long prompt must not silently truncate.
        assert wire["tokenizer"].max_len == 512


class TestUnclipNegativeSide:
    def test_wrong_width_negative_pooled_zeroed_for_unclip(self, monkeypatch):
        """The uncond half of CFG must get the same treatment as the cond
        half: a 1024-wide text pooled on the negative conditioning of an
        sd21-unclip-class model (context 1024) is dropped to zeros, not fed
        into label_emb."""
        import dataclasses

        import jax
        import jax.numpy as jnp

        from comfyui_parallelanything_tpu.models import build_unet, sd15_config
        from comfyui_parallelanything_tpu.nodes import TPUKSampler

        # context_dim 1024 marks the unclip family for the width gate; keep
        # every other dim tiny.
        cfg = sd15_config(
            model_channels=32, channel_mult=(1, 2), transformer_depth=(1, 1),
            attention_levels=(0, 1), context_dim=1024, num_heads=4,
            norm_groups=8, adm_in_channels=32, prediction="v",
            dtype=jnp.float32,
        )
        model = build_unet(cfg, jax.random.key(0), sample_shape=(1, 8, 8, 4))
        (out,) = TPUKSampler().sample(
            model,
            {"context": jnp.zeros((1, 3, 1024))},
            {"samples": jnp.zeros((1, 8, 8, 4))}, seed=0, steps=2, cfg=3.0,
            sampler_name="euler", scheduler="normal",
            negative={"context": jnp.zeros((1, 3, 1024)),
                      "pooled": jnp.zeros((1, 1024))},  # text-tower width
        )
        assert np.isfinite(np.asarray(out["samples"])).all()


class TestI2VClipFeaOnClipless:
    def test_clip_fea_dropped_with_warning_on_wan22_checkpoint(self, caplog):
        """WAN2.1 template (clip_vision_output wired) reused on a WAN2.2-style
        i2v checkpoint (36 channels, no img_emb): stock ignores clip_fea —
        the composition drops it with a warning instead of raising
        mid-sampling."""
        import jax
        import jax.numpy as jnp

        from comfyui_parallelanything_tpu.models import build_wan
        from comfyui_parallelanything_tpu.models.wan import (
            WanConfig,
            apply_i2v_conditioning,
        )

        wcfg = WanConfig(
            in_channels=12, out_channels=4, hidden_size=48, ffn_dim=96,
            num_heads=4, depth=1, text_dim=32, freq_dim=16,
            dtype=jnp.float32,  # no img_dim: WAN2.2-style
        )
        dit = build_wan(
            wcfg, jax.random.key(0), sample_shape=(1, 2, 4, 4, 12), txt_len=6
        )
        cond = jnp.zeros((1, 2, 4, 4, 8))
        composed = apply_i2v_conditioning(
            dit, cond, clip_fea=jnp.ones((1, 5, 24))
        )
        out = composed.apply(
            composed.params, jnp.zeros((1, 2, 4, 4, 4)), jnp.array([0.5]),
            jnp.zeros((1, 6, 32)),
        )
        assert out.shape == (1, 2, 4, 4, 4)
        assert np.isfinite(np.asarray(out)).all()


class TestMaskAndUtilityShims:
    """The round-5 utility family: mask ops, batch utils, conditioning
    concat, the refiner text encode — the stock builtins inpaint/refiner
    template exports lean on beyond the core loop."""

    def _nodes(self):
        from comfyui_parallelanything_tpu.nodes_compat import (
            stock_node_mappings,
        )

        return stock_node_mappings()

    def test_conditioning_concat_token_axis(self):
        import jax.numpy as jnp

        n = self._nodes()
        to = {"context": jnp.ones((2, 3, 8)), "pooled": jnp.ones((2, 8))}
        frm = {"context": jnp.zeros((1, 5, 8))}
        (out,) = n["ConditioningConcat"]().concat(to, frm)
        assert out["context"].shape == (2, 8, 8)
        assert out["pooled"].shape == (2, 8)  # to's fields win
        with pytest.raises(ValueError, match="widths"):
            n["ConditioningConcat"]().concat(
                to, {"context": jnp.zeros((1, 5, 4))}
            )

    def test_refiner_encode_over_dual_wire(self, tmp_path, monkeypatch):
        import jax.numpy as jnp

        from comfyui_parallelanything_tpu.nodes import NODE_CLASS_MAPPINGS

        env = _synthetic_sdxl_env(tmp_path, monkeypatch)
        _, clip, _ = (
            NODE_CLASS_MAPPINGS["CheckpointLoaderSimple"]().load(env["ckpt"])
        )
        n = self._nodes()
        (c,) = n["CLIPTextEncodeSDXLRefiner"]().encode(
            clip, ascore=6.0, width=1024, height=1024,
            text="a watercolor lighthouse",
        )
        g_hidden = clip["g"]["encoder"].cfg.hidden_size
        g_pool = clip["g"]["encoder"].cfg.projection_dim
        assert c["context"].shape[-1] == g_hidden  # G stream alone
        assert c["pooled"].shape[-1] == g_pool + 5 * 256
        with pytest.raises(ValueError, match="G-tower"):
            n["CLIPTextEncodeSDXLRefiner"]().encode(
                {"encoder": None}, 6.0, 1024, 1024, "x"
            )

    def test_mask_family_roundtrip(self):
        import jax.numpy as jnp
        import numpy as np

        n = self._nodes()
        (m,) = n["SolidMask"]().solid(0.25, width=8, height=4)
        assert m.shape == (1, 4, 8) and float(m[0, 0, 0]) == 0.25
        (inv,) = n["InvertMask"]().invert(m)
        assert float(inv[0, 0, 0]) == 0.75
        (img,) = n["MaskToImage"]().mask_to_image(m)
        assert img.shape == (1, 4, 8, 3)
        (back,) = n["ImageToMask"]().image_to_mask(img, "green")
        np.testing.assert_allclose(np.asarray(back), np.asarray(m))
        # 3-channel image has no alpha: fully-opaque mask.
        (ones,) = n["ImageToMask"]().image_to_mask(img, "alpha")
        assert float(ones.min()) == 1.0

    def test_grow_mask_dilates_and_erodes(self):
        import jax.numpy as jnp
        import numpy as np

        n = self._nodes()
        m = jnp.zeros((1, 7, 7)).at[0, 3, 3].set(1.0)
        (grown,) = n["GrowMask"]().expand_mask(m, 1, tapered_corners=True)
        assert float(grown.sum()) == 5.0  # plus-shaped kernel
        (grown_sq,) = n["GrowMask"]().expand_mask(m, 1, tapered_corners=False)
        assert float(grown_sq.sum()) == 9.0  # full 3x3
        (shrunk,) = n["GrowMask"]().expand_mask(grown_sq, -1,
                                                tapered_corners=False)
        np.testing.assert_allclose(np.asarray(shrunk), np.asarray(m))
        (same,) = n["GrowMask"]().expand_mask(m, 0)
        np.testing.assert_allclose(np.asarray(same), np.asarray(m))

    def test_feather_and_composite(self):
        import jax.numpy as jnp
        import numpy as np

        n = self._nodes()
        (m,) = n["SolidMask"]().solid(1.0, width=8, height=8)
        (f,) = n["FeatherMask"]().feather(m, left=4, top=0, right=0, bottom=0)
        got = np.asarray(f)[0, 4, :4]
        np.testing.assert_allclose(got, [0.25, 0.5, 0.75, 1.0], atol=1e-6)

        dst = jnp.zeros((1, 6, 6)).at[:, :, :].set(0.5)
        src = jnp.ones((1, 2, 2))
        (add,) = n["MaskComposite"]().combine(dst, src, x=4, y=4,
                                              operation="add")
        assert float(add[0, 5, 5]) == 1.0 and float(add[0, 0, 0]) == 0.5
        (sub,) = n["MaskComposite"]().combine(dst, src, x=0, y=0,
                                              operation="subtract")
        assert float(sub[0, 0, 0]) == 0.0
        (xor,) = n["MaskComposite"]().combine(dst, src, x=0, y=0,
                                              operation="xor")
        # round(0.5) banker's-rounds to 0; xor(0, 1) = 1.
        assert float(xor[0, 0, 0]) == 1.0
        assert float(xor[0, 5, 5]) == 0.5  # outside the paste window: untouched

    def test_image_batch_and_latent_batch_utils(self):
        import jax.numpy as jnp

        n = self._nodes()
        a = jnp.zeros((2, 8, 8, 3))
        b = jnp.ones((1, 4, 4, 3))
        (batched,) = n["ImageBatch"]().batch(a, b)
        assert batched.shape == (3, 8, 8, 3)

        lat = {"samples": jnp.arange(4.0).reshape(4, 1, 1, 1),
               "noise_mask": jnp.ones((4, 2, 2, 1))}
        (rep,) = n["RepeatLatentBatch"]().repeat(lat, 2)
        assert rep["samples"].shape[0] == 8
        assert rep["noise_mask"].shape[0] == 8
        (sl,) = n["LatentFromBatch"]().frombatch(lat, batch_index=1, length=2)
        assert sl["samples"].shape[0] == 2
        assert float(sl["samples"][0, 0, 0, 0]) == 1.0
        assert sl["noise_mask"].shape[0] == 2

        # A mask batch smaller than the samples batch cycles up (stock
        # repeat_to_batch_size) before tiling/slicing — never lands empty or
        # at a batch matching neither the latents nor 1.
        short = {"samples": jnp.zeros((4, 1, 1, 1)),
                 "noise_mask": jnp.ones((2, 2, 2, 1))}
        (rep2,) = n["RepeatLatentBatch"]().repeat(short, 3)
        assert rep2["samples"].shape[0] == 12
        assert rep2["noise_mask"].shape[0] == 12
        (sl2,) = n["LatentFromBatch"]().frombatch(short, batch_index=2,
                                                  length=2)
        assert sl2["noise_mask"].shape[0] == 2

    def test_load_image_mask_channels(self, tmp_path, monkeypatch):
        import numpy as np
        from PIL import Image

        n = self._nodes()
        in_dir = tmp_path / "input"
        in_dir.mkdir()
        rgba = np.zeros((4, 4, 4), np.uint8)
        rgba[..., 0] = 255  # red
        rgba[..., 3] = 0    # fully transparent
        Image.fromarray(rgba, "RGBA").save(in_dir / "m.png")
        monkeypatch.setenv("PA_INPUT_DIR", str(in_dir))
        (alpha,) = n["LoadImageMask"]().load_image("m.png", "alpha")
        assert float(alpha.min()) == 1.0  # stock 1-alpha: transparent -> 1
        (red,) = n["LoadImageMask"]().load_image("m.png", "red")
        assert float(red.max()) == 1.0 and red.shape == (1, 4, 4)

    def test_refiner_checkpoint_sniffs_and_samples(self, tmp_path,
                                                   monkeypatch):
        """The real refiner story: a refiner-shaped single-file checkpoint
        sniffs as sdxl-refiner (G-only 1280 context, label_emb, no shallow
        attention), loads its bundled G tower as a plain CLIP wire, and a
        stock refiner graph (CLIPTextEncodeSDXLRefiner ×2 → KSampler)
        denoises."""
        from comfyui_parallelanything_tpu.host import run_workflow
        from comfyui_parallelanything_tpu.models import (
            load_safetensors,
            sniff_model_family,
        )

        env = _synthetic_refiner_env(tmp_path, monkeypatch)
        assert sniff_model_family(load_safetensors(env["ckpt"])) == \
            "sdxl-refiner"
        monkeypatch.setenv("PA_OUTPUT_DIR", str(tmp_path / "out"))
        wf = {
            "4": {"class_type": "CheckpointLoaderSimple",
                  "inputs": {"ckpt_name": env["ckpt"]}},
            "5": {"class_type": "EmptyLatentImage",
                  "inputs": {"width": 32, "height": 32, "batch_size": 1}},
            "6": {"class_type": "CLIPTextEncodeSDXLRefiner",
                  "inputs": {"ascore": 6.0, "width": 1024, "height": 1024,
                             "text": "a watercolor lighthouse",
                             "clip": ["4", 1]}},
            "7": {"class_type": "CLIPTextEncodeSDXLRefiner",
                  "inputs": {"ascore": 2.5, "width": 1024, "height": 1024,
                             "text": "blurry", "clip": ["4", 1]}},
            "3": {"class_type": "KSampler",
                  "inputs": {"seed": 3, "steps": 2, "cfg": 4.0,
                             "sampler_name": "euler", "scheduler": "normal",
                             "denoise": 0.3, "model": ["4", 0],
                             "positive": ["6", 0], "negative": ["7", 0],
                             "latent_image": ["5", 0]}},
            "8": {"class_type": "VAEDecode",
                  "inputs": {"samples": ["3", 0], "vae": ["4", 2]}},
        }
        out = run_workflow(wf)
        images = np.asarray(out["8"][0])
        assert images.shape[0] == 1 and np.isfinite(images).all()

    def test_tiled_vae_nodes_match_untiled(self, tmp_path, monkeypatch):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from comfyui_parallelanything_tpu.models import build_vae
        from tests.test_vae import TINY as TINY_VAE

        n = self._nodes()
        vae = build_vae(TINY_VAE, jax.random.key(0), sample_hw=16)
        lat = jax.random.normal(
            jax.random.key(1), (1, 16, 16, TINY_VAE.z_channels)
        )
        # 2024+ stock exports carry overlap/temporal widgets — must be
        # accepted (host.py passes every workflow input as a kwarg).
        (tiled,) = n["VAEDecodeTiled"]().decode(
            {"samples": lat}, vae, tile_size=64, overlap=32,
            temporal_size=64, temporal_overlap=8,
        )
        from comfyui_parallelanything_tpu.models.vae import (
            vae_output_to_images,
        )

        plain = vae_output_to_images(vae.decode(lat))
        assert tiled.shape == plain.shape
        np.testing.assert_allclose(np.asarray(tiled), np.asarray(plain),
                                   atol=0.08)  # ramp-blend seams, bf16 dots
        px = jnp.clip(plain, 0.0, 1.0)
        (enc,) = n["VAEEncodeTiled"]().encode(px, vae, tile_size=64,
                                              overlap=32)
        # Factor-unaligned tile sizes floor gracefully through the owner
        # (encode_maybe_tiled), not a ValueError — 17 is unaligned for any
        # spatial factor > 1.
        (enc2,) = n["VAEEncodeTiled"]().encode(px, vae, tile_size=17)
        assert np.isfinite(np.asarray(enc2["samples"])).all()
        plain_z = vae.encode(
            jnp.asarray(px) * 2.0 - 1.0
        )
        assert enc["samples"].shape == plain_z.shape
        assert np.isfinite(np.asarray(enc["samples"])).all()

    def test_freeu_patch(self):
        import jax
        import jax.numpy as jnp

        from comfyui_parallelanything_tpu.models import build_unet, sd15_config

        n = self._nodes()
        # model_channels*4 / *2 widths must occur in the up path for the
        # patch to bite: full channel_mult ladder at tiny width.
        cfg = sd15_config(
            model_channels=8, channel_mult=(1, 2, 4, 4), num_res_blocks=1,
            attention_levels=(0,), transformer_depth=(1, 0, 0, 0),
            num_heads=2, context_dim=16, norm_groups=4, dtype=jnp.float32,
        )
        m = build_unet(cfg, jax.random.key(0), sample_shape=(1, 16, 16, 4))
        x = jax.random.normal(jax.random.key(1), (1, 16, 16, 4))
        t = jnp.array([300.0])
        ctx = jax.random.normal(jax.random.key(2), (1, 4, 16))
        base_out = np.asarray(m(x, t, ctx))

        # Neutral parameters (b=1, s=1) are an identity patch.
        (neutral,) = n["FreeU_V2"]().patch(m, b1=1.0, b2=1.0, s1=1.0, s2=1.0)
        np.testing.assert_allclose(np.asarray(neutral(x, t, ctx)), base_out,
                                   rtol=1e-4, atol=1e-4)
        # Real parameters change the output; params are shared, not copied.
        (patched,) = n["FreeU_V2"]().patch(m, b1=1.3, b2=1.4, s1=0.9, s2=0.2)
        assert patched.params is m.params
        assert not np.allclose(np.asarray(patched(x, t, ctx)), base_out,
                               atol=1e-4)
        (v1,) = n["FreeU"]().patch(m, b1=1.1, b2=1.2, s1=0.9, s2=0.2)
        out_v1 = np.asarray(v1(x, t, ctx))
        assert not np.allclose(out_v1, np.asarray(patched(x, t, ctx)),
                               atol=1e-4)  # v1 != v2 math
        with pytest.raises(ValueError, match="UNET"):
            n["FreeU_V2"]().patch(
                type("M", (), {"config": None, "params": {}})(),
                1.3, 1.4, 0.9, 0.2,
            )

    def test_rescale_cfg_patch_honored_by_sampler(self):
        import jax.numpy as jnp

        from comfyui_parallelanything_tpu.models.api import DiffusionModel
        from comfyui_parallelanything_tpu.sampling.runner import run_sampler

        n = self._nodes()

        def apply(p, x, t, context=None, **kw):
            # Per-SAMPLE context mean (cond/uncond halves differ under the
            # batched-CFG call) + a spatial gradient so the prediction has a
            # nonzero std for rescale_guidance to act on.
            m = jnp.mean(context, axis=(1, 2)).reshape((-1, 1, 1, 1))
            ramp = jnp.linspace(0.0, 1.0, x.shape[1]).reshape((1, -1, 1, 1))
            return x * 0.1 + m * (0.5 + ramp)

        m = DiffusionModel(apply=apply, params={}, name="toy")
        (tagged,) = n["RescaleCFG"]().patch(m, 0.9)
        assert tagged.sampler_prefs == {"cfg_rescale": 0.9}
        assert tagged is not m and m.sampler_prefs is None

        noise = jnp.ones((1, 8, 8, 4))
        ctx = jnp.ones((1, 3, 5))
        unc = jnp.zeros((1, 3, 5)) - 1.0
        kw = dict(sampler="euler", steps=3, cfg_scale=7.0,
                  uncond_context=unc, rng=None)
        base = run_sampler(m, noise, ctx, **kw)
        tagged_out = run_sampler(tagged, noise, ctx, **kw)
        explicit = run_sampler(m, noise, ctx, cfg_rescale=0.9, **kw)
        # The tag changes the result exactly like the explicit widget value.
        assert not np.allclose(np.asarray(tagged_out), np.asarray(base),
                               atol=1e-6)
        np.testing.assert_allclose(np.asarray(tagged_out),
                                   np.asarray(explicit), atol=1e-6)

        # The stock ordering wraps AFTER patching: prefs must survive
        # parallelize (the ParallelModel carries them through).
        import comfyui_parallelanything_tpu as pa

        pm = pa.parallelize(tagged, pa.DeviceChain.even(["cpu:0"]))
        assert pm.sampler_prefs == {"cfg_rescale": 0.9}
        pm_out = run_sampler(pm, noise, ctx, **kw)
        np.testing.assert_allclose(np.asarray(pm_out), np.asarray(explicit),
                                   atol=1e-5)
        # Guard: the sibling prediction patch must REJECT a wrapped model
        # with its written guidance, not an opaque TypeError.
        with pytest.raises(ValueError, match="before ParallelAnything"):
            n["ModelSamplingDiscrete"]().patch(pm, "v_prediction")
        pm.cleanup()

    def test_model_sampling_discrete(self):
        from comfyui_parallelanything_tpu.models import build_unet, sd15_config

        n = self._nodes()
        import jax
        import jax.numpy as jnp

        cfg = sd15_config(
            model_channels=8, channel_mult=(1, 2), num_res_blocks=1,
            attention_levels=(1,), transformer_depth=(0, 1), num_heads=2,
            context_dim=16, norm_groups=4, dtype=jnp.float32,
        )
        m = build_unet(cfg, jax.random.key(0), sample_shape=(1, 8, 8, 4))
        assert m.config.prediction == "eps"
        (v,) = n["ModelSamplingDiscrete"]().patch(m, "v_prediction",
                                                  zsnr=False)
        assert v.config.prediction == "v" and v.params is m.params
        assert m.config.prediction == "eps"  # original untouched
        (back,) = n["ModelSamplingDiscrete"]().patch(v, "eps")
        assert back.config.prediction == "eps"
        with pytest.raises(ValueError, match="not.*supported"):
            n["ModelSamplingDiscrete"]().patch(m, "lcm")

    def test_empty_video_latent(self):
        n = self._nodes()
        (lat,) = n["EmptyHunyuanLatentVideo"]().generate(
            width=848, height=480, length=25, batch_size=2
        )
        assert lat["samples"].shape == (2, 7, 60, 106, 16)
        # Off-schedule lengths floor to 4k+1 like stock (API submissions
        # bypass widget steps): 10 -> 9 pixel frames -> 3 latent frames.
        (lat2,) = n["EmptyHunyuanLatentVideo"]().generate(64, 64, 10)
        assert lat2["samples"].shape == (1, 3, 8, 8, 16)

    def test_conditioning_set_mask_node(self):
        import jax.numpy as jnp

        n = self._nodes()
        cond = {"context": jnp.ones((1, 3, 5)), "area": (4, 4, 0, 0),
                "extras": ({"context": jnp.ones((1, 2, 5))},)}
        mask = jnp.ones((1, 8, 8))
        (out,) = n["ConditioningSetMask"]().append(cond, mask, strength=0.5,
                                                   set_cond_area="default")
        # Stock keeps the area (the denoiser composes box × mask), stores
        # the mask strength under its OWN key (area strength and mask
        # strength multiply — a shared key would clobber), and maps the tag
        # over combined extras too (conditioning_set_values rule).
        assert out["area"] == (4, 4, 0, 0)
        assert "strength" not in out  # SetMask never touches area strength
        assert out["mask_strength"] == 0.5 and out["mask"].shape == (1, 8, 8)
        assert out["extras"][0]["mask"].shape == (1, 8, 8)
        assert out["extras"][0]["mask_strength"] == 0.5

    def test_sampler_custom_matches_advanced(self):
        import jax.numpy as jnp

        from comfyui_parallelanything_tpu.models.api import DiffusionModel
        from comfyui_parallelanything_tpu.nodes import (
            TPUBasicScheduler,
            TPUKSamplerSelect,
            TPURandomNoise,
            TPUCFGGuider,
            TPUSamplerCustomAdvanced,
        )

        n = self._nodes()

        def apply(p, x, t, context=None, **kw):
            m = jnp.mean(context, axis=(1, 2)).reshape((-1, 1, 1, 1))
            return x * 0.05 + m
        model = DiffusionModel(apply=apply, params={},
                               config=type("C", (), {"prediction": "eps"})())
        pos = {"context": jnp.ones((1, 3, 5))}
        neg = {"context": jnp.zeros((1, 3, 5))}
        lat = {"samples": jnp.zeros((1, 8, 8, 4))}
        (samp,) = TPUKSamplerSelect().get_sampler("euler")
        (sig,) = TPUBasicScheduler().get_sigmas(model, "normal", 4, 1.0)
        (out, den) = n["SamplerCustom"]().sample(
            model, True, 11, 3.0, pos, neg, samp, sig, lat
        )
        (noise,) = TPURandomNoise().get_noise(11)
        (guider,) = TPUCFGGuider().get_guider(model, pos, neg, 3.0)
        (out2, _) = TPUSamplerCustomAdvanced().sample(
            noise, guider, samp, sig, lat
        )
        np.testing.assert_allclose(np.asarray(out["samples"]),
                                   np.asarray(out2["samples"]), atol=1e-6)
        assert np.isfinite(np.asarray(den["samples"])).all()

    def test_image_invert(self):
        import jax.numpy as jnp

        n = self._nodes()
        (inv,) = n["ImageInvert"]().invert(jnp.full((1, 2, 2, 3), 0.25))
        assert float(inv[0, 0, 0, 0]) == 0.75


class TestPatchSourcePreservation:
    def test_patches_keep_loader_source_tag(self, tmp_path, monkeypatch):
        """Every model-patch shim must keep the loader's source tag — the
        LoraLoader shims re-bake from the original file through it. `source`
        is a DiffusionModel FIELD precisely so dc.replace carries it."""
        from comfyui_parallelanything_tpu.nodes import NODE_CLASS_MAPPINGS

        paths = _synthetic_stock_env(tmp_path, monkeypatch)
        model, _, _ = (
            NODE_CLASS_MAPPINGS["CheckpointLoaderSimple"]().load(paths["ckpt"])
        )
        assert model.source["family"] == "sd15"
        from comfyui_parallelanything_tpu.nodes_compat import (
            FreeU_V2,
            ModelSamplingDiscrete,
            RescaleCFG,
        )

        (a,) = FreeU_V2().patch(model, 1.3, 1.4, 0.9, 0.2)
        (b,) = RescaleCFG().patch(a, 0.7)
        (c,) = ModelSamplingDiscrete().patch(b, "v_prediction")
        assert c.source == model.source
        assert c.sampler_prefs == {"cfg_rescale": 0.7}
        assert c.config.freeu is not None and c.config.prediction == "v"


class TestCustomSamplingSchedulers:
    def _nodes(self):
        from comfyui_parallelanything_tpu.nodes_compat import (
            stock_node_mappings,
        )

        return stock_node_mappings()

    def test_karras_and_exponential_nodes(self):
        n = self._nodes()
        (sig,) = n["KarrasScheduler"]().get_sigmas(
            steps=10, sigma_max=14.6, sigma_min=0.03, rho=7.0
        )
        s = np.asarray(sig)
        assert len(s) == 11 and s[-1] == 0.0 and np.all(np.diff(s[:-1]) < 0)
        assert s[0] == pytest.approx(14.6, rel=1e-4)
        (sig2,) = n["ExponentialScheduler"]().get_sigmas(
            steps=8, sigma_max=10.0, sigma_min=0.1
        )
        s2 = np.asarray(sig2)
        assert len(s2) == 9 and s2[-1] == 0.0
        assert s2[0] == pytest.approx(10.0, rel=1e-4)

    def test_sd_turbo_schedule(self):
        n = self._nodes()
        (sig,) = n["SDTurboScheduler"]().get_sigmas(None, steps=1,
                                                    denoise=1.0)
        s = np.asarray(sig)
        # One step from the TOP of the trained ladder, then 0.
        assert len(s) == 2 and s[-1] == 0.0
        from comfyui_parallelanything_tpu.sampling.k_samplers import (
            model_sigmas,
        )
        from comfyui_parallelanything_tpu.sampling.schedules import (
            scaled_linear_schedule,
        )

        table = np.asarray(model_sigmas(scaled_linear_schedule()))
        assert s[0] == pytest.approx(table[-1], rel=1e-5)
        # Stock offset rule: start = 10 − int(10·denoise); fractional rungs
        # floor (denoise=0.75 → start 3 → timestep 699 — the stock value).
        (sig2,) = n["SDTurboScheduler"]().get_sigmas(None, steps=2,
                                                     denoise=0.5)
        s2 = np.asarray(sig2)
        assert s2[0] == pytest.approx(table[499], rel=1e-5)
        assert len(s2) == 3 and np.all(np.diff(s2) < 0)
        (sig3,) = n["SDTurboScheduler"]().get_sigmas(None, steps=1,
                                                     denoise=0.75)
        assert np.asarray(sig3)[0] == pytest.approx(table[699], rel=1e-5)
        # Past-the-ladder slices TRUNCATE (no repeated sigmas — those NaN
        # the multistep SDE samplers).
        (sig4,) = n["SDTurboScheduler"]().get_sigmas(None, steps=8,
                                                     denoise=0.3)
        s4 = np.asarray(sig4)
        assert len(s4) == 4 and np.all(np.diff(s4) < 0)  # 3 rungs + 0
        import types
        flowish = types.SimpleNamespace(
            config=types.SimpleNamespace(prediction="flow"))
        with pytest.raises(ValueError, match="flow"):
            n["SDTurboScheduler"]().get_sigmas(flowish, steps=1)

    def test_named_sampler_nodes(self):
        n = self._nodes()
        for name, want in (("SamplerEulerAncestral", "euler_ancestral"),
                           ("SamplerDPMPP_2M_SDE", "dpmpp_2m_sde"),
                           ("SamplerDPMPP_SDE", "dpmpp_sde"),
                           ("SamplerDPMPP_3M_SDE", "dpmpp_3m_sde"),
                           ("SamplerLMS", "lms")):
            # Stock variants carry eta/s_noise widgets — absorbed.
            (wire,) = n[name]().get_sampler(eta=1.0, s_noise=1.0)
            assert wire == {"sampler": want}


class TestImageAndLatentOps:
    def _nodes(self):
        from comfyui_parallelanything_tpu.nodes_compat import (
            stock_node_mappings,
        )

        return stock_node_mappings()

    def test_image_crop_blur_sharpen(self):
        import jax.numpy as jnp

        n = self._nodes()
        img = jnp.zeros((1, 16, 16, 3)).at[:, 8, 8, :].set(1.0)
        (c,) = n["ImageCrop"]().crop(img, width=8, height=4, x=4, y=6)
        assert c.shape == (1, 4, 8, 3)
        (b,) = n["ImageBlur"]().blur(img, blur_radius=2, sigma=1.0)
        assert b.shape == img.shape
        # Blur spreads the impulse: center drops, neighbor rises.
        assert float(b[0, 8, 8, 0]) < 1.0 and float(b[0, 8, 9, 0]) > 0.0
        assert float(jnp.sum(b)) == pytest.approx(float(jnp.sum(img)),
                                                  rel=1e-3)  # energy kept
        (s,) = n["ImageSharpen"]().sharpen(img, sharpen_radius=2, sigma=1.0,
                                           alpha=1.0)
        assert s.shape == img.shape
        assert float(s[0, 8, 8, 0]) == 1.0  # clipped at 1 after boost

    def test_latent_math(self):
        import jax.numpy as jnp

        n = self._nodes()
        a = {"samples": jnp.ones((2, 4, 4, 4))}
        b = {"samples": jnp.full((1, 4, 4, 4), 2.0)}  # batch-1 cycles up
        (add,) = n["LatentAdd"]().op(a, b)
        assert float(add["samples"][1, 0, 0, 0]) == 3.0
        (sub,) = n["LatentSubtract"]().op(a, b)
        assert float(sub["samples"][0, 0, 0, 0]) == -1.0
        (mul,) = n["LatentMultiply"]().op(a, 0.5)
        assert float(mul["samples"][0, 0, 0, 0]) == 0.5
        (bl,) = n["LatentBlend"]().blend(a, b, 0.25)
        assert float(bl["samples"][0, 0, 0, 0]) == pytest.approx(
            1.0 * 0.25 + 2.0 * 0.75)
        (bat,) = n["LatentBatch"]().batch(a, b)
        assert bat["samples"].shape[0] == 3
        # Interpolate: ratio=1 returns samples1 exactly (direction and
        # magnitude both degenerate to a's).
        (it,) = n["LatentInterpolate"]().op(a, b, 1.0)
        np.testing.assert_allclose(np.asarray(it["samples"]),
                                   np.asarray(a["samples"]), atol=1e-6)
        # Midpoint of parallel latents: magnitudes lerp (1 and 2 -> 1.5).
        (mid,) = n["LatentInterpolate"]().op(a, b, 0.5)
        np.testing.assert_allclose(np.asarray(mid["samples"]),
                                   1.5 * np.ones((2, 4, 4, 4)), atol=1e-6)
        # Spatial mismatch resizes (stock reshape_latent_to).
        small = {"samples": jnp.ones((1, 2, 2, 4))}
        (add2,) = n["LatentAdd"]().op(a, small)
        assert add2["samples"].shape == (2, 4, 4, 4)


def test_latent_math_channel_mismatch_raises():
    import jax.numpy as jnp

    from comfyui_parallelanything_tpu.nodes_compat import stock_node_mappings

    n = stock_node_mappings()
    a = {"samples": jnp.ones((1, 4, 4, 4))}
    b = {"samples": jnp.ones((1, 4, 4, 16))}
    with pytest.raises(ValueError, match="channel counts differ"):
        n["LatentAdd"]().op(a, b)


def test_conditioning_set_area_percentage_and_flux_encode():
    import jax.numpy as jnp

    from comfyui_parallelanything_tpu.nodes_compat import stock_node_mappings

    n = stock_node_mappings()
    cond = {"context": jnp.ones((1, 3, 5)),
            "extras": ({"context": jnp.ones((1, 2, 5))},)}
    (out,) = n["ConditioningSetAreaPercentage"]().append(
        cond, width=0.5, height=0.25, x=0.1, y=0.2, strength=0.8
    )
    assert out["area_pct"] == (0.25, 0.5, 0.2, 0.1)
    assert out["extras"][0]["area_pct"] == (0.25, 0.5, 0.2, 0.1)
    # CLIPTextEncodeFlux rejects non-flux wires with guidance.
    with pytest.raises(ValueError, match="flux"):
        n["CLIPTextEncodeFlux"]().encode({"type": "clip"}, "a", "b", 3.5)


def test_area_forms_replace_each_other():
    import jax.numpy as jnp

    from comfyui_parallelanything_tpu.nodes_compat import stock_node_mappings

    n = stock_node_mappings()
    cond = {"context": jnp.ones((1, 3, 5))}
    (px,) = n["ConditioningSetArea"]().append(cond, 512, 512, 0, 0, 1.0)
    (pct,) = n["ConditioningSetAreaPercentage"]().append(
        px, width=0.25, height=0.25, x=0.0, y=0.0, strength=1.0
    )
    assert pct["area"] is None and pct["area_pct"] is not None
    (px2,) = n["ConditioningSetArea"]().append(pct, 256, 256, 0, 0, 1.0)
    assert px2["area_pct"] is None and px2["area"] == (32, 32, 0, 0)


def test_scale_to_megapixels_and_model_merge():
    import jax
    import jax.numpy as jnp

    from comfyui_parallelanything_tpu.models import build_unet, sd15_config
    from comfyui_parallelanything_tpu.nodes_compat import stock_node_mappings

    n = stock_node_mappings()
    (img,) = n["ImageScaleToTotalPixels"]().upscale(
        jnp.zeros((1, 100, 400, 3)), "bilinear", 0.04  # 0.04 MP ≈ 41943 px
    )
    B, H, W, C = img.shape
    assert abs(H * W - 0.04 * 1024 * 1024) / (0.04 * 1024 * 1024) < 0.05
    assert abs(W / H - 4.0) < 0.2  # aspect preserved
    with pytest.raises(ValueError, match="upscale_method"):
        n["ImageScaleToTotalPixels"]().upscale(jnp.zeros((1, 8, 8, 3)),
                                               "hermite", 1.0)

    cfg = sd15_config(
        model_channels=8, channel_mult=(1, 2), num_res_blocks=1,
        attention_levels=(1,), transformer_depth=(0, 1), num_heads=2,
        context_dim=16, norm_groups=4, dtype=jnp.float32,
    )
    m1 = build_unet(cfg, jax.random.key(0), sample_shape=(1, 8, 8, 4))
    m2 = build_unet(cfg, jax.random.key(1), sample_shape=(1, 8, 8, 4))
    (merged,) = n["ModelMergeSimple"]().merge(m1, m2, 0.25)
    leaf1 = jax.tree.leaves(m1.params)[0]
    leaf2 = jax.tree.leaves(m2.params)[0]
    got = jax.tree.leaves(merged.params)[0]
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(leaf1) * 0.25
                               + np.asarray(leaf2) * 0.75, atol=1e-6)
    assert merged.source == {"merged": True}
    from comfyui_parallelanything_tpu.nodes_compat import LoraLoader
    with pytest.raises(ValueError, match="BEFORE"):
        LoraLoader().load_lora(merged, {"type": "clip"}, "x.safetensors")
    x = jnp.zeros((1, 8, 8, 4)); t = jnp.array([5.0])
    ctx = jnp.zeros((1, 3, 16))
    assert np.isfinite(np.asarray(merged(x, t, ctx))).all()
    # Cross-topology merge fails loudly.
    cfg2 = sd15_config(
        model_channels=8, channel_mult=(1, 2, 2), num_res_blocks=1,
        attention_levels=(1,), transformer_depth=(0, 1, 0), num_heads=2,
        context_dim=16, norm_groups=4, dtype=jnp.float32,
    )
    m3 = build_unet(cfg2, jax.random.key(2), sample_shape=(1, 8, 8, 4))
    with pytest.raises(ValueError, match="cannot merge"):
        n["ModelMergeSimple"]().merge(m1, m3, 0.5)


# ---------------------------------------------------------------------------
# SD3 stock surface: TripleCLIPLoader, DualCLIPLoader(type=sd3),
# ModelSamplingSD3/ModelSamplingFlux, and the stock SD3 template chain.
# ---------------------------------------------------------------------------


def _synthetic_sd3_towers(tmp_path, monkeypatch):
    """Tiny clip_l / clip_g / t5xxl tower files in the stock SD3 template
    naming, with tokenizer env vars wired and the tiny configs pinned. The
    widths are coupled the way the real family's are: T5 d_model (128) is the
    context width the CLIP L⊕G joint (64+64) pads to; pooled = 64+64."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import torch
    import transformers
    from safetensors.numpy import save_file

    import comfyui_parallelanything_tpu.models as models_pkg
    import comfyui_parallelanything_tpu.models.text_encoders as te_mod
    from comfyui_parallelanything_tpu.models.text_encoders import (
        build_clip_text,
        open_clip_g_config,
    )
    from tests.test_text_encoders import (
        TINY_CLIP,
        TINY_T5,
        TestOpenCLIPConversion,
        _hf_clip,
    )

    l_cfg = dataclasses.replace(TINY_CLIP, max_len=77)
    monkeypatch.setattr(te_mod, "clip_l_config", lambda: l_cfg)
    g_cfg = open_clip_g_config(
        vocab_size=100, hidden_size=64, num_layers=2, num_heads=4,
        max_len=77, projection_dim=64, dtype=jnp.float32,
    )
    monkeypatch.setattr(models_pkg, "open_clip_g_config", lambda: g_cfg)
    monkeypatch.setattr(te_mod, "open_clip_g_config", lambda: g_cfg)
    t5_cfg = dataclasses.replace(TINY_T5, d_model=128)
    monkeypatch.setattr(te_mod, "t5_xxl_config", lambda: t5_cfg)

    hf_l = _hf_clip(l_cfg, "quick_gelu")
    l_path = tmp_path / "clip_l.safetensors"
    save_file(
        {k: np.ascontiguousarray(v.detach().numpy())
         for k, v in hf_l.state_dict().items()},
        str(l_path),
    )

    g_enc = build_clip_text(g_cfg, rng=jax.random.key(2))
    g_path = tmp_path / "clip_g.safetensors"
    save_file(
        {k: np.ascontiguousarray(v)
         for k, v in TestOpenCLIPConversion._openclip_layout(
             g_cfg, g_enc.params
         ).items()},
        str(g_path),
    )

    hf_cfg = transformers.T5Config(
        vocab_size=t5_cfg.vocab_size, d_model=t5_cfg.d_model,
        d_kv=t5_cfg.d_kv, d_ff=t5_cfg.d_ff, num_layers=t5_cfg.num_layers,
        num_heads=t5_cfg.num_heads,
        relative_attention_num_buckets=t5_cfg.relative_buckets,
        relative_attention_max_distance=t5_cfg.relative_max_distance,
        feed_forward_proj="gated-gelu", dropout_rate=0.0,
    )
    torch.manual_seed(3)
    hf_t5 = transformers.T5EncoderModel(hf_cfg).eval()
    t5_path = tmp_path / "t5xxl_fp16.safetensors"
    save_file(
        {k: np.ascontiguousarray(v.detach().numpy())
         for k, v in hf_t5.state_dict().items()},
        str(t5_path),
    )

    _word_level_tokenizer(tmp_path, monkeypatch)  # PA_TOKENIZER_JSON
    tokenizers = pytest.importorskip("tokenizers")
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace

    vocab = {"[UNK]": 0, "</s>": 1, "a": 5, "watercolor": 6, "lighthouse": 7,
             "at": 8, "dawn": 9, "blurry": 10}
    t = tokenizers.Tokenizer(WordLevel(vocab, unk_token="[UNK]"))
    t.pre_tokenizer = Whitespace()
    t5_tok = tmp_path / "t5_tokenizer.json"
    t.save(str(t5_tok))
    monkeypatch.setenv("PA_T5_TOKENIZER_JSON", str(t5_tok))

    return {"l": str(l_path), "g": str(g_path), "t5": str(t5_path)}


class TestTripleCLIPLoader:
    def test_loads_and_encodes_sd3_conditioning(self, tmp_path, monkeypatch):
        from comfyui_parallelanything_tpu.nodes import TPUTextEncode
        from comfyui_parallelanything_tpu.nodes_compat import TripleCLIPLoader

        paths = _synthetic_sd3_towers(tmp_path, monkeypatch)
        # Scrambled widget order: classification is by name/keys, not slot.
        (clip,) = TripleCLIPLoader().load(paths["t5"], paths["g"], paths["l"])
        assert clip["type"] == "sd3-triple"
        assert clip["t5"] is not None

        (cond,) = TPUTextEncode().encode(clip, "a watercolor lighthouse")
        # context: CLIP joint (77 tokens, padded 64+64→128) ‖ T5 (77, 128)
        assert cond["context"].shape == (1, 154, 128)
        assert cond["pooled"].shape == (1, 128)
        assert np.isfinite(np.asarray(cond["context"])).all()
        # The T5 half must be the live stream, not padding.
        assert float(np.abs(np.asarray(cond["context"][:, 77:])).max()) > 0

    def test_key_signature_classification(self, tmp_path, monkeypatch):
        """Files with no name markers classify off the safetensors keys."""
        import shutil

        from comfyui_parallelanything_tpu.nodes_compat import (
            TripleCLIPLoader,
            _classify_text_tower,
        )

        paths = _synthetic_sd3_towers(tmp_path, monkeypatch)
        a = tmp_path / "towerA.safetensors"  # t5 keys
        b = tmp_path / "towerB.safetensors"  # open-clip keys
        c = tmp_path / "towerC.safetensors"  # HF CLIP keys, width 64
        shutil.copy(paths["t5"], a)
        shutil.copy(paths["g"], b)
        shutil.copy(paths["l"], c)
        assert _classify_text_tower(str(a), str(a)) == "t5"
        assert _classify_text_tower(str(b), str(b)) == "open-clip-g"
        assert _classify_text_tower(str(c), str(c)) == "clip-l"
        (clip,) = TripleCLIPLoader().load(str(b), str(c), str(a))
        assert clip["type"] == "sd3-triple" and clip["t5"] is not None

    def test_duplicate_and_missing_towers_raise(self, tmp_path, monkeypatch):
        from comfyui_parallelanything_tpu.nodes_compat import TripleCLIPLoader

        paths = _synthetic_sd3_towers(tmp_path, monkeypatch)
        with pytest.raises(ValueError, match="two t5 files"):
            TripleCLIPLoader().load(paths["t5"], paths["t5"], paths["l"])

    def test_dual_clip_loader_sd3_two_tower_form(self, tmp_path, monkeypatch):
        """DualCLIPLoader(type=sd3): CLIP-L + G, no T5 — context is the
        padded joint alone; a clip_g file in slot 1 corrects swapped wiring."""
        from comfyui_parallelanything_tpu.nodes import TPUTextEncode
        from comfyui_parallelanything_tpu.nodes_compat import DualCLIPLoader

        paths = _synthetic_sd3_towers(tmp_path, monkeypatch)
        (clip,) = DualCLIPLoader().load(paths["g"], paths["l"], type="sd3")
        assert clip["type"] == "sd3-triple" and clip["t5"] is None
        (cond,) = TPUTextEncode().encode(clip, "a watercolor lighthouse")
        # No T5 stream: the joint pads to the real family's 4096.
        assert cond["context"].shape == (1, 77, 4096)
        assert cond["pooled"].shape == (1, 128)

    def test_dual_clip_loader_sd3_clip_plus_t5_pairings(self, tmp_path,
                                                        monkeypatch):
        """DualCLIPLoader(type=sd3) with the common clip+t5xxl pairings:
        stock classifies the two files from their contents, so the T5 file
        must land on the t5 slot (not mis-load as a CLIP tower) and the
        missing CLIP tower zero-fills at encode."""
        from comfyui_parallelanything_tpu.nodes import TPUTextEncode
        from comfyui_parallelanything_tpu.nodes_compat import DualCLIPLoader

        paths = _synthetic_sd3_towers(tmp_path, monkeypatch)
        # clip_l + t5xxl (either order): g stays None.
        (clip,) = DualCLIPLoader().load(paths["t5"], paths["l"], type="sd3")
        assert clip["type"] == "sd3-triple"
        assert clip["g"] is None
        assert clip["l"] is not None and clip["t5"] is not None
        (cond,) = TPUTextEncode().encode(clip, "a watercolor lighthouse")
        # CLIP joint (L only, padded to the tiny T5's 128) ‖ T5 stream.
        assert cond["context"].shape == (1, 154, 128)
        # Missing G pooled zero-fills at the canonical 1280: 64 + 1280.
        assert cond["pooled"].shape == (1, 1344)
        assert float(np.abs(np.asarray(cond["pooled"][:, 64:])).max()) == 0.0
        # The T5 half must be the live stream, not padding.
        assert float(np.abs(np.asarray(cond["context"][:, 77:])).max()) > 0
        # clip_g + t5xxl: l stays None, pooled = zeros(768) ⊕ G's 64.
        (clip2,) = DualCLIPLoader().load(paths["g"], paths["t5"], type="sd3")
        assert clip2["l"] is None and clip2["t5"] is not None
        (cond2,) = TPUTextEncode().encode(clip2, "a watercolor lighthouse")
        assert cond2["pooled"].shape == (1, 832)
        assert float(np.abs(np.asarray(cond2["pooled"][:, :768])).max()) == 0.0
        # ALIGNMENT: the missing L still occupies its LEADING joint slot as
        # zeros (canonical 768, clamped to the tiny geometry: min(768,
        # 128−64) = 64), so G's live features keep their trained offset
        # instead of shifting to column 0.
        assert cond2["context"].shape == (1, 154, 128)
        clip_rows = np.asarray(cond2["context"][:, :77])
        assert float(np.abs(clip_rows[..., :64]).max()) == 0.0
        assert float(np.abs(clip_rows[..., 64:]).max()) > 0

    def test_dual_clip_loader_sd3_duplicate_towers_raise(self, tmp_path,
                                                         monkeypatch):
        import pytest

        from comfyui_parallelanything_tpu.nodes_compat import DualCLIPLoader

        paths = _synthetic_sd3_towers(tmp_path, monkeypatch)
        with pytest.raises(ValueError, match="two t5 files"):
            DualCLIPLoader().load(paths["t5"], paths["t5"], type="sd3")


class TestModelSamplingShiftPatches:
    def _model(self, prefs=None):
        from types import SimpleNamespace

        return SimpleNamespace(
            sampler_prefs=prefs,
            config=SimpleNamespace(prediction="flow"),
        )

    def test_sd3_patch_sets_pref_and_resolution_order(self):
        from comfyui_parallelanything_tpu.nodes import _shift_from_prefs
        from comfyui_parallelanything_tpu.nodes_compat import ModelSamplingSD3

        (m,) = ModelSamplingSD3().patch(self._model(), shift=3.0)
        assert m.sampler_prefs["shift"] == 3.0
        # Widget default yields to the patch; an explicit value wins.
        assert _shift_from_prefs(m, 1.15) == 3.0
        assert _shift_from_prefs(m, 2.0) == 2.0
        assert _shift_from_prefs(self._model(), 1.15) == 1.15

    def test_flux_patch_log_interpolates_over_tokens(self):
        import math

        from comfyui_parallelanything_tpu.nodes_compat import ModelSamplingFlux

        (m,) = ModelSamplingFlux().patch(self._model())  # 1024² defaults
        assert m.sampler_prefs["shift"] == pytest.approx(math.exp(1.15))
        (m2,) = ModelSamplingFlux().patch(self._model(), width=256, height=256)
        assert m2.sampler_prefs["shift"] == pytest.approx(math.exp(0.5))

    def test_dataclass_model_keeps_type_and_existing_prefs(self):
        import dataclasses

        from comfyui_parallelanything_tpu.nodes_compat import ModelSamplingSD3

        @dataclasses.dataclass
        class M:
            sampler_prefs: dict | None = None

        (m,) = ModelSamplingSD3().patch(
            M(sampler_prefs={"cfg_rescale": 0.5}), shift=5.0
        )
        assert isinstance(m, M)
        assert m.sampler_prefs == {"cfg_rescale": 0.5, "shift": 5.0}

    def test_basic_scheduler_honors_pref(self):
        from comfyui_parallelanything_tpu.nodes import TPUBasicScheduler

        (s_pref,) = TPUBasicScheduler().get_sigmas(
            self._model({"shift": 3.0}), "normal", 8, 1.0
        )
        (s_expl,) = TPUBasicScheduler().get_sigmas(
            self._model(), "normal", 8, 1.0, shift=3.0
        )
        np.testing.assert_allclose(np.asarray(s_pref), np.asarray(s_expl))
        (s_plain,) = TPUBasicScheduler().get_sigmas(
            self._model(), "normal", 8, 1.0
        )
        assert not np.allclose(np.asarray(s_pref), np.asarray(s_plain))


class TestStockSD3Template:
    def test_sd3_template_chain(self, tmp_path, monkeypatch):
        """The stock SD3 template node chain — UNETLoader (MMDiT file sniffed
        sd3-medium) + TripleCLIPLoader + CLIPTextEncode ×2 + ModelSamplingSD3
        + EmptySD3LatentImage + KSampler — runs with stock names/inputs."""
        import dataclasses

        import jax
        import jax.numpy as jnp
        from safetensors.numpy import save_file

        import comfyui_parallelanything_tpu.models as models_pkg
        from comfyui_parallelanything_tpu import nodes_compat
        from comfyui_parallelanything_tpu.models.mmdit import (
            MMDiTConfig,
            build_mmdit,
        )
        from tests.test_mmdit import _official_layout_sd

        paths = _synthetic_sd3_towers(tmp_path, monkeypatch)
        mcfg = MMDiTConfig(
            in_channels=16, depth=2, context_in_dim=128, pooled_dim=128,
            pos_embed_max=16, qk_norm=True, dtype=jnp.float32,
        )
        mm = build_mmdit(
            mcfg, jax.random.key(0), sample_shape=(1, 8, 8, 16), txt_len=6
        )
        mm_path = tmp_path / "sd3_tiny.safetensors"
        save_file(
            {k: np.ascontiguousarray(v)
             for k, v in _official_layout_sd(mcfg, mm.params).items()},
            str(mm_path),
        )
        monkeypatch.setattr(models_pkg, "sd3_medium_config", lambda: mcfg)

        n = nodes_compat.stock_node_mappings()
        (model,) = n["UNETLoader"]().load_unet(str(mm_path))
        (clip,) = n["TripleCLIPLoader"]().load(
            paths["l"], paths["g"], paths["t5"]
        )
        (pos,) = n["CLIPTextEncode"]().run(
            clip=clip, text="a watercolor lighthouse at dawn"
        )
        (neg,) = n["CLIPTextEncode"]().run(clip=clip, text="blurry")
        (model,) = n["ModelSamplingSD3"]().patch(model, shift=3.0)
        (lat,) = n["EmptySD3LatentImage"]().generate(64, 64, 1)
        assert lat["samples"].shape == (1, 8, 8, 16)
        (out,) = n["KSampler"]().run(
            model=model, positive=pos, negative=neg, latent_image=lat,
            seed=0, steps=2, cfg=3.0, sampler_name="euler",
            scheduler="normal",
        )
        assert out["samples"].shape == (1, 8, 8, 16)
        assert np.isfinite(np.asarray(out["samples"])).all()


class TestLatentTransforms:
    def _lat(self, arr, mask=None):
        d = {"samples": arr}
        if mask is not None:
            d["noise_mask"] = mask
        return d

    def test_flip_axes_and_mask_follow(self):
        import jax.numpy as jnp

        from comfyui_parallelanything_tpu.nodes_compat import LatentFlip

        x = jnp.arange(2 * 3 * 4 * 2, dtype=jnp.float32).reshape(2, 3, 4, 2)
        m = jnp.arange(2 * 3 * 4 * 1, dtype=jnp.float32).reshape(2, 3, 4, 1)
        (v,) = LatentFlip().flip(self._lat(x, m), "x-axis: vertically")
        np.testing.assert_array_equal(np.asarray(v["samples"]),
                                      np.asarray(x)[:, ::-1])
        np.testing.assert_array_equal(np.asarray(v["noise_mask"]),
                                      np.asarray(m)[:, ::-1])
        (h,) = LatentFlip().flip(self._lat(x), "y-axis: horizontally")
        np.testing.assert_array_equal(np.asarray(h["samples"]),
                                      np.asarray(x)[:, :, ::-1])
        # Video latents (NTHWC): the same −3/−2 spatial axes.
        v5 = jnp.arange(2 * 2 * 3 * 4 * 2, dtype=jnp.float32).reshape(
            2, 2, 3, 4, 2
        )
        (out5,) = LatentFlip().flip(self._lat(v5), "x-axis: vertically")
        np.testing.assert_array_equal(np.asarray(out5["samples"]),
                                      np.asarray(v5)[:, :, ::-1])

    def test_rotate_clockwise_quarters_compose(self):
        import jax.numpy as jnp

        from comfyui_parallelanything_tpu.nodes_compat import LatentRotate

        x = jnp.arange(1 * 2 * 3 * 1, dtype=jnp.float32).reshape(1, 2, 3, 1)
        (r90,) = LatentRotate().rotate(self._lat(x), "90 degrees")
        assert r90["samples"].shape == (1, 3, 2, 1)
        # Clockwise: the top-left element lands top-right.
        np.testing.assert_array_equal(
            np.asarray(r90["samples"])[0, :, :, 0],
            np.rot90(np.asarray(x)[0, :, :, 0], k=-1),
        )
        (r270,) = LatentRotate().rotate(r90, "270 degrees")
        np.testing.assert_array_equal(np.asarray(r270["samples"]),
                                      np.asarray(x))
        (r0,) = LatentRotate().rotate(self._lat(x), "none")
        np.testing.assert_array_equal(np.asarray(r0["samples"]), np.asarray(x))

    def test_crop_clamps_to_bounds(self):
        import jax.numpy as jnp

        from comfyui_parallelanything_tpu.nodes_compat import LatentCrop

        x = jnp.arange(1 * 16 * 16 * 4, dtype=jnp.float32).reshape(1, 16, 16, 4)
        (c,) = LatentCrop().crop(self._lat(x), width=32, height=16, x=8, y=16)
        assert c["samples"].shape == (1, 2, 4, 4)
        np.testing.assert_array_equal(np.asarray(c["samples"]),
                                      np.asarray(x)[:, 2:4, 1:5])
        # Stock boundary rule: the origin clamps to (dim − 8) latent units and
        # the slice truncates — an out-of-range window yields a
        # smaller-than-requested latent anchored at the clamp, it does NOT
        # slide back to preserve the requested size.
        (c2,) = LatentCrop().crop(self._lat(x), width=96, height=96,
                                  x=512, y=512)
        assert c2["samples"].shape == (1, 8, 8, 4)
        np.testing.assert_array_equal(np.asarray(c2["samples"]),
                                      np.asarray(x)[:, 8:, 8:])
        # In-range origin with an oversized window: truncated, not shrunk to
        # fit beforehand (requested 12 latent cols from col 8 of 16 → 8).
        (c3,) = LatentCrop().crop(self._lat(x), width=96, height=16,
                                  x=64, y=0)
        assert c3["samples"].shape == (1, 2, 8, 4)
        np.testing.assert_array_equal(np.asarray(c3["samples"]),
                                      np.asarray(x)[:, 0:2, 8:])

    def test_save_load_round_trip_and_legacy_rescale(self, tmp_path,
                                                     monkeypatch):
        import jax.numpy as jnp
        from safetensors.numpy import save_file

        from comfyui_parallelanything_tpu.nodes_compat import (
            LoadLatent,
            SaveLatent,
        )

        monkeypatch.setenv("PA_OUTPUT_DIR", str(tmp_path / "out"))
        monkeypatch.setenv("PA_INPUT_DIR", str(tmp_path / "out"))
        # Non-square + distinct channel count so a layout mix-up cannot hide.
        x = jnp.linspace(-2, 2, 1 * 2 * 6 * 4).reshape(1, 2, 6, 4)
        ui = SaveLatent().save(self._lat(x), "latents/ComfyUI")
        fname = ui["ui"]["latents"][0]
        # The FILE stores the public stock layout: channels-first NCHW.
        from safetensors.numpy import load_file

        on_disk = load_file(
            str(tmp_path / "out" / "latents" / fname)
        )
        assert on_disk["latent_tensor"].shape == (1, 4, 2, 6)
        np.testing.assert_allclose(
            on_disk["latent_tensor"],
            np.moveaxis(np.asarray(x, np.float32), -1, 1), atol=1e-7,
        )
        (lat,) = LoadLatent().load(os.path.join("latents", fname))
        np.testing.assert_allclose(np.asarray(lat["samples"]), np.asarray(x),
                                   atol=1e-7)
        # Legacy (pre-version-marker) dumps are stock files too — NCHW,
        # stored scaled by 0.18215.
        legacy = tmp_path / "out" / "legacy.latent"
        save_file(
            {"latent_tensor": np.ascontiguousarray(
                np.moveaxis(np.asarray(x, np.float32), -1, 1) * 0.18215)},
            str(legacy),
        )
        (lat2,) = LoadLatent().load("legacy.latent")
        np.testing.assert_allclose(np.asarray(lat2["samples"]),
                                   np.asarray(x), atol=1e-5)
        with pytest.raises(ValueError, match="not found"):
            LoadLatent().load("ghost.latent")
