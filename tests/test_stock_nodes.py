"""Stock-ComfyUI node-name shims (nodes_compat.py): a workflow exported from
a stock ComfyUI install — builtin class names, builtin input keys — runs
against this host unchanged.

The reference pack lives inside ComfyUI and gets the builtins for free
(any_device_parallel.py:1473-1483 registers only its own nodes); here the
builtin names are part of the host-parity surface. Family sniffing
(models/loader.sniff_model_family) replaces the stock loader's implicit
config detection.
"""

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

    def test_sniffs_synthetic_sd15_checkpoint(self, stock_env):
        # The same synthetic checkpoint the e2e test loads must sniff sd15.
        paths = stock_env
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

    def tiny_sd15(**kw):
        # the swap lasts a whole file of tests (``stock_env``): a test that
        # asks the preset for other sizes still gets them
        return real_sd15(**{**dict(
            model_channels=32, channel_mult=(1, 2), transformer_depth=(1, 1),
            attention_levels=(0, 1), context_dim=TINY_CLIP.hidden_size,
            num_heads=4, norm_groups=8, dtype=jnp.float32,
        ), **kw})

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


@pytest.fixture(scope="module")
def stock_env(tmp_path_factory):
    """``_synthetic_stock_env`` once a file of tests: the checkpoint, the
    tokenizer and the tiny presets stay until the file's last test. Read-only
    — a test that moves or rewrites the checkpoint takes a copy."""
    with pytest.MonkeyPatch.context() as mp:
        yield _synthetic_stock_env(tmp_path_factory.mktemp("stock_env"), mp)


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

    def test_exported_stock_workflow_runs_unchanged(self, stock_env, tmp_path,
                                                    monkeypatch):
        paths = stock_env
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

    def test_stock_conditioning_and_image_shims_run(self, stock_env, tmp_path,
                                                    monkeypatch):
        # regional prompting (SetArea → Combine),
        # prompt blending (Average), stock image resize, and PreviewImage —
        # one exported-style graph exercising all of them.
        paths = stock_env
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

    def test_conditioning_zero_out_and_sdxl_encode(self, stock_env):
        import jax.numpy as jnp

        from comfyui_parallelanything_tpu.nodes import NODE_CLASS_MAPPINGS

        paths = stock_env
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

    def test_models_dir_resolution(self, stock_env, tmp_path, monkeypatch):
        # ComfyUI folder layout: a bare name resolves via
        # $PA_MODELS_DIR/checkpoints/<name>.
        paths = stock_env
        import shutil

        models = tmp_path / "models" / "checkpoints"
        models.mkdir(parents=True)
        shutil.copy(paths["ckpt"], models / "tiny.safetensors")
        monkeypatch.setenv("PA_MODELS_DIR", str(tmp_path / "models"))

        wf = self._stock_workflow("tiny.safetensors")
        del wf["9"]  # no image save needed for the resolution check
        out = run_workflow(wf)
        assert out["8"][0].shape[0] == 2

    def test_clip_set_last_layer_tags_wire(self, stock_env):
        paths = stock_env
        wf = self._stock_workflow(paths["ckpt"])
        del wf["9"]
        wf["10"] = {"class_type": "CLIPSetLastLayer",
                    "inputs": {"clip": ["4", 1], "stop_at_clip_layer": -2}}
        wf["6"]["inputs"]["clip"] = ["10", 0]
        out = run_workflow(wf)
        assert np.isfinite(np.asarray(out["8"][0])).all()

    def test_missing_tokenizer_fails_with_instructions(self, stock_env,
                                                       monkeypatch):
        paths = stock_env
        monkeypatch.delenv("PA_TOKENIZER_JSON")
        wf = self._stock_workflow(paths["ckpt"])
        with pytest.raises(Exception, match="PA_TOKENIZER_JSON"):
            run_workflow(wf)

    def test_stock_custom_sampling_graph_executes(self, stock_env):
        # The custom-sampling path exactly as a stock FLUX-style export wires
        # it: RandomNoise + KSamplerSelect + BasicScheduler + CFGGuider +
        # SamplerCustomAdvanced under their stock names and stock input keys.
        paths = stock_env
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

    def test_lora_loader_rebakes_from_source(self, stock_env, tmp_path):
        from safetensors.numpy import save_file

        from comfyui_parallelanything_tpu.models import load_safetensors
        from comfyui_parallelanything_tpu.nodes import NODE_CLASS_MAPPINGS

        paths = stock_env
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

    def test_lora_loader_strength_clip_bakes_text_tower(self, stock_env,
                                                        tmp_path):
        # A LoRA with kohya lora_te_* keys must rebuild the CLIP wire with the
        # deltas baked into the bundled tower (the
        # strength_clip divergence closed).
        from safetensors.numpy import save_file

        from comfyui_parallelanything_tpu.models import load_safetensors
        from comfyui_parallelanything_tpu.nodes import NODE_CLASS_MAPPINGS

        paths = stock_env
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

    def test_lora_loader_attaches_serving_delegate(self, stock_env, tmp_path):
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

        paths = stock_env
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

    def test_base_refiner_template_runs_unchanged(self, stock_env, tmp_path,
                                                  monkeypatch):
        """The stock SDXL base→refiner API export shape — two checkpoint
        loaders, four text encodes, chained KSamplerAdvanced — runs as-is
        (the tiny sd15 synthetic checkpoint stands in for both stages; the
        node surface and window semantics are family-independent)."""
        paths = stock_env
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
    def test_unet_loader_bare_diffusion_file(self, stock_env, tmp_path):
        from safetensors.numpy import save_file

        from comfyui_parallelanything_tpu.models import load_safetensors
        from comfyui_parallelanything_tpu.nodes_compat import UNETLoader

        paths = stock_env
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

    def test_lora_loader_model_only(self, stock_env, tmp_path):
        import jax
        from safetensors.numpy import save_file

        from comfyui_parallelanything_tpu.models import load_safetensors
        from comfyui_parallelanything_tpu.nodes import NODE_CLASS_MAPPINGS

        paths = stock_env
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

    def test_clip_loader_single_tower(self, stock_env, tmp_path, monkeypatch):
        from safetensors.numpy import save_file

        from comfyui_parallelanything_tpu.nodes_compat import CLIPLoader
        import comfyui_parallelanything_tpu.models.text_encoders as te_mod
        from tests.test_text_encoders import TINY_CLIP, _hf_clip
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


class TestPatchSourcePreservation:
    def test_patches_keep_loader_source_tag(self, stock_env):
        """Every model-patch shim must keep the loader's source tag — the
        LoraLoader shims re-bake from the original file through it. `source`
        is a DiffusionModel FIELD precisely so dc.replace carries it."""
        from comfyui_parallelanything_tpu.nodes import NODE_CLASS_MAPPINGS

        paths = stock_env
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
