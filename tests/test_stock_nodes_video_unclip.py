"""Stock-ComfyUI node-name shims (nodes_compat.py), the video and unCLIP
families: the stock WAN i2v / t2v templates on a tiny synthetic WAN world
(written once for this file), the sd21-unclip single file and its adm vector.
``test_stock_nodes.py`` has the stock graph itself and the helpers."""

import os

import numpy as np
import pytest

from comfyui_parallelanything_tpu.host import run_workflow
from comfyui_parallelanything_tpu.models.loader import sniff_model_family
from tests.test_stock_nodes import _word_level_tokenizer


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


@pytest.fixture(scope="module")
def wan_env(tmp_path_factory):
    """``_synthetic_wan_env`` once for this file (both templates read it)."""
    with pytest.MonkeyPatch.context() as mp:
        yield _synthetic_wan_env(tmp_path_factory.mktemp("wan_env"), mp)


class TestStockWanI2VWorkflow:
    def test_wan_i2v_template_runs_unchanged(self, wan_env, tmp_path,
                                             monkeypatch):
        """The stock WAN image-to-video API export shape — UNETLoader +
        CLIPLoader(wan) + VAELoader + CLIPVisionLoader/Encode +
        WanImageToVideo + KSampler + VAEDecode + SaveAnimatedWEBP — runs
        as-is on the tiny synthetic WAN i2v world."""
        paths = wan_env
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
    def test_wan_t2v_template_runs_unchanged(self, wan_env, tmp_path,
                                             monkeypatch):
        """The stock WAN text-to-video API export shape — UNETLoader +
        CLIPLoader(wan) + VAELoader + EmptyHunyuanLatentVideo (the t2v
        latent entry) + KSampler + VAEDecode + SaveAnimatedWEBP — runs
        as-is on the tiny synthetic WAN world."""
        paths = wan_env
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
