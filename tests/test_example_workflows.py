"""The committed examples/*.json must stay runnable: every one is executed
through host.py against a synthetic tiny checkpoint written once for this
file (``example_env``), with only the things a user would edit rewritten —
file paths, device ids, sizes/steps."""

import json

import jax
import numpy as np
import pytest

from comfyui_parallelanything_tpu.host import run_workflow


def _synthetic_env(tmp_path, monkeypatch):
    """Tiny sd15 checkpoint + CLIP encoder + tokenizer on disk, with the
    family preset factories monkeypatched to the matching tiny configs.
    Returns (paths dict, vae spatial factor)."""
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
        # the swap lasts the whole file (``example_env``): a helper that swaps
        # the preset again on top of this one passes its own sizes through
        return real_sd15(**{**dict(
            model_channels=32, channel_mult=(1, 2), transformer_depth=(1, 1),
            attention_levels=(0, 1), context_dim=TINY_CLIP.hidden_size,
            num_heads=4, norm_groups=8, dtype=jnp.float32,
        ), **kw})

    monkeypatch.setattr(models_pkg, "sd15_config", tiny_sd15)
    monkeypatch.setattr(models_pkg, "sd_vae_config", lambda: TINY_VAE)
    monkeypatch.setattr(te_mod, "clip_l_config", lambda: TINY_CLIP)

    # Synthetic full checkpoint: diffusion + bundled VAE subtrees, in the
    # torch/ldm key layout the converters consume.
    ucfg = tiny_sd15()
    unet = build_unet(ucfg, jax.random.key(0), sample_shape=(1, 8, 8, 4))
    vae = build_vae(TINY_VAE, jax.random.key(1), sample_hw=16)
    sd = {
        f"model.diffusion_model.{k}": np.ascontiguousarray(v)
        for k, v in _ldm_sd(ucfg, unet.params).items()
    }
    sd.update(
        {
            f"first_stage_model.{k}": np.ascontiguousarray(v)
            for k, v in _ldm_layout_sd(TINY_VAE, vae.params).items()
        }
    )
    ckpt = tmp_path / "ckpt.safetensors"
    save_file(sd, str(ckpt))

    # Synthetic CLIP encoder (HF text_model layout) + tokenizer.json.
    hf = _hf_clip(TINY_CLIP, "quick_gelu")
    clip_sd = {
        k: np.ascontiguousarray(v.detach().numpy())
        for k, v in hf.state_dict().items()
    }
    enc_path = tmp_path / "clip.safetensors"
    save_file(clip_sd, str(enc_path))

    tokenizers = pytest.importorskip("tokenizers")
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace

    vocab = {"[UNK]": 0, "a": 5, "watercolor": 6, "lighthouse": 7, "at": 8,
             "dawn": 9, "blurry": 10, "low": 11, "quality": 12}
    t = tokenizers.Tokenizer(WordLevel(vocab, unk_token="[UNK]"))
    t.pre_tokenizer = Whitespace()
    tok_path = tmp_path / "tokenizer.json"
    t.save(str(tok_path))
    paths = {
        "ckpt": str(ckpt), "clip": str(enc_path), "tok": str(tok_path),
        "max_len": TINY_CLIP.max_len,
    }
    return paths, vae.spatial_factor


@pytest.fixture(scope="module")
def example_env(tmp_path_factory):
    """``_synthetic_env`` once for this file: (paths, the tiny VAE's spatial
    factor), the tiny presets in place until the file's last test. Read-only."""
    with pytest.MonkeyPatch.context() as mp:
        yield _synthetic_env(tmp_path_factory.mktemp("example_env"), mp)


class TestShippedExampleWorkflow:
    """The committed examples/*.json must stay runnable: execute them through
    host.py against a synthetic tiny checkpoint (inverse-synthesis layout, the
    tests' standard pattern), with only the things a user would edit rewritten
    — file paths, device ids, sizes/steps. Every node class in the shipped
    artifacts executes for real."""

    def _rewrite_common(self, wf, paths):
        wf["checkpoint"]["inputs"]["ckpt_path"] = paths["ckpt"]
        wf["clip"]["inputs"]["encoder_path"] = paths["clip"]
        wf["clip"]["inputs"]["tokenizer_json"] = paths["tok"]
        wf["clip"]["inputs"]["max_len"] = paths["max_len"]
        wf["dev0"]["inputs"]["device_id"] = "cpu:0"
        wf["dev1"]["inputs"]["device_id"] = "cpu:1"
        wf["sampler"]["inputs"]["steps"] = 2
        return wf

    def test_example_sd15_txt2img_executes(self, cpu_devices, example_env, tmp_path):
        import os

        paths, factor = example_env
        wf = self._rewrite_common(
            json.load(open("examples/workflow_sd15_txt2img.json")), paths
        )
        wf["latent"]["inputs"].update(width=32, height=32, batch_size=4)
        wf["save"]["inputs"]["output_dir"] = str(tmp_path / "out")

        out = run_workflow(wf)
        images = out["decode"][0]
        # TPUEmptyLatent assumes the SD factor-8 latent grid; the tiny VAE
        # upsamples by its own (smaller) factor — assert consistently.
        hw = 32 // 8 * factor
        assert images.shape == (4, hw, hw, 3)
        assert np.isfinite(np.asarray(images)).all()
        assert out["parallel"][0].devices == ("cpu:0", "cpu:1")
        saved = out["save"][0]
        assert len(saved) == 4 and all(os.path.exists(p) for p in saved)

    def test_example_custom_sampling_executes(self, cpu_devices, example_env, tmp_path):
        import os

        paths, factor = example_env
        wf = json.load(open("examples/workflow_custom_sampling.json"))
        wf["checkpoint"]["inputs"]["ckpt_path"] = paths["ckpt"]
        wf["clip"]["inputs"]["encoder_path"] = paths["clip"]
        wf["clip"]["inputs"]["tokenizer_json"] = paths["tok"]
        wf["clip"]["inputs"]["max_len"] = paths["max_len"]
        wf["dev0"]["inputs"]["device_id"] = "cpu:0"
        wf["dev1"]["inputs"]["device_id"] = "cpu:1"
        wf["sigmas"]["inputs"]["steps"] = 2
        wf["latent"]["inputs"].update(width=32, height=32, batch_size=4)
        wf["save"]["inputs"]["output_dir"] = str(tmp_path / "out")

        out = run_workflow(wf)
        images = out["decode"][0]
        hw = 32 // 8 * factor
        assert images.shape == (4, hw, hw, 3)
        assert np.isfinite(np.asarray(images)).all()
        saved = out["save"][0]
        assert len(saved) == 4 and all(os.path.exists(p) for p in saved)

    def test_example_sd15_controlnet_executes(self, cpu_devices, example_env, tmp_path):
        import os

        from PIL import Image
        from safetensors.numpy import save_file

        import comfyui_parallelanything_tpu.models as models_pkg
        from comfyui_parallelanything_tpu.models import build_controlnet
        from tests.test_controlnet import _ldm_controlnet_sd, _randomized_cn

        paths, factor = example_env
        # Tiny ControlNet checkpoint for the (monkeypatched) tiny sd15 config.
        cfg = models_pkg.sd15_config()
        cn = build_controlnet(cfg, jax.random.key(5), sample_shape=(1, 4, 4, 4))
        cn_sd = _ldm_controlnet_sd(cfg, _randomized_cn(cn, cfg).params)
        cn_path = tmp_path / "cn.safetensors"
        save_file({k: np.ascontiguousarray(v) for k, v in cn_sd.items()},
                  str(cn_path))
        hint_path = tmp_path / "hint.png"
        Image.fromarray(
            (np.random.default_rng(3).uniform(0, 1, (32, 32, 3)) * 255)
            .astype(np.uint8)
        ).save(hint_path)

        wf = self._rewrite_common(
            json.load(open("examples/workflow_sd15_controlnet.json")), paths
        )
        wf["latent"]["inputs"].update(width=32, height=32, batch_size=2)
        wf["hint"]["inputs"]["image_path"] = str(hint_path)
        wf["controlnet"]["inputs"]["ckpt_path"] = str(cn_path)
        wf["save"]["inputs"]["output_dir"] = str(tmp_path / "out")

        out = run_workflow(wf)
        images = out["decode"][0]
        hw = 32 // 8 * factor
        assert images.shape == (2, hw, hw, 3)
        assert np.isfinite(np.asarray(images)).all()
        saved = out["save"][0]
        assert len(saved) == 2 and all(os.path.exists(p) for p in saved)

    def test_example_sd15_img2img_executes(self, cpu_devices, example_env, tmp_path):
        import os

        from PIL import Image

        paths, factor = example_env
        src = tmp_path / "input.png"
        Image.fromarray(
            (np.random.default_rng(0).uniform(0, 1, (16, 16, 3)) * 255).astype(
                np.uint8
            )
        ).save(src)
        wf = self._rewrite_common(
            json.load(open("examples/workflow_sd15_img2img.json")), paths
        )
        wf["source"]["inputs"]["image_path"] = str(src)
        wf["save"]["inputs"]["output_dir"] = str(tmp_path / "out")

        out = run_workflow(wf)
        images = out["decode"][0]
        lat = 16 // factor  # encode downsamples by the tiny VAE's factor
        assert out["sampler"][0]["samples"].shape[1:3] == (lat, lat)
        assert images.shape == (1, lat * factor, lat * factor, 3)
        assert np.isfinite(np.asarray(images)).all()
        saved = out["save"][0]
        assert len(saved) == 1 and os.path.exists(saved[0])


    def test_example_inpaint_outpaint_executes(self, cpu_devices, example_env, tmp_path):
        import os

        from PIL import Image

        paths, factor = example_env
        src = tmp_path / "input.png"
        Image.fromarray(
            (np.random.default_rng(0).uniform(0, 1, (16, 16, 3)) * 255).astype(
                np.uint8
            )
        ).save(src)
        wf = self._rewrite_common(
            json.load(open("examples/workflow_sd15_inpaint_outpaint.json")),
            paths,
        )
        wf["source"]["inputs"]["image_path"] = str(src)
        # Tiny-scale the outpaint extension to the synthetic world.
        wf["outpaint_pad"]["inputs"].update(left=8, right=8, feathering=4)
        wf["save"]["inputs"]["output_dir"] = str(tmp_path / "out")

        out = run_workflow(wf)
        images = out["paste_back"][0]
        # 16px source + 8px pad each side; decode returns the padded frame.
        assert images.shape == (1, 16, 32, 3)
        assert np.isfinite(np.asarray(images)).all()
        # The source interior survives the paste-back (mask is 0 there away
        # from the feather band).
        src_px = np.asarray(Image.open(src), np.float32)[None] / 255.0
        np.testing.assert_allclose(
            np.asarray(images[:, 4:12, 14:18, :]),
            src_px[:, 4:12, 6:10, :], atol=0.35,
        )
        saved = out["save"][0]
        assert len(saved) == 1 and os.path.exists(saved[0])

    def test_example_hiresfix_executes(self, cpu_devices, example_env, tmp_path):
        import os

        import jax
        from safetensors.numpy import save_file

        from comfyui_parallelanything_tpu.models.upscale import (
            UpscaleConfig,
            build_upscaler,
        )
        from tests.test_upscale import _modern_sd

        import jax.numpy as jnp

        paths, factor = example_env
        ucfg = UpscaleConfig(nf=8, nb=1, gc=4, scale=4, dtype=jnp.float32)
        up = build_upscaler(ucfg, jax.random.key(7))
        up_path = tmp_path / "esrgan_tiny.safetensors"
        save_file(
            {k: np.ascontiguousarray(v)
             for k, v in _modern_sd(ucfg, up.params).items()},
            str(up_path),
        )
        wf = self._rewrite_common(
            json.load(open("examples/workflow_sd15_hiresfix.json")), paths
        )
        wf["latent"]["inputs"].update(width=32, height=32, batch_size=1)
        wf["hires_pass"]["inputs"]["steps"] = 2
        wf["esrgan"]["inputs"]["ckpt_path"] = str(up_path)
        wf["final_upscale"]["inputs"]["tile"] = 0
        wf["save"]["inputs"]["output_dir"] = str(tmp_path / "out")

        out = run_workflow(wf)
        hw = 32 // 8 * factor  # base latent grid through the tiny VAE
        base = out["decode"][0]
        assert base.shape == (1, 2 * hw, 2 * hw, 3)  # latent-upscaled 2x
        final = out["final_upscale"][0]
        assert final.shape == (1, 8 * hw, 8 * hw, 3)  # ESRGAN x4 on top
        assert np.isfinite(np.asarray(final)).all()
        saved = out["save"][0]
        assert len(saved) == 1 and os.path.exists(saved[0])


class TestShippedStockExample:
    def test_example_stock_txt2img_executes(self, tmp_path, monkeypatch):
        """The stock-named example (pure ComfyUI builtin class names, the
        shape a stock export has) runs through the compat shims against the
        synthetic checkpoint — only user-editable fields rewritten."""
        import os

        from tests.test_stock_nodes import _synthetic_stock_env

        paths = _synthetic_stock_env(tmp_path, monkeypatch)
        monkeypatch.setenv("PA_OUTPUT_DIR", str(tmp_path / "out"))
        wf = json.load(open("examples/workflow_stock_sd15_txt2img.json"))
        wf["4"]["inputs"]["ckpt_name"] = paths["ckpt"]
        wf["5"]["inputs"].update(width=32, height=32, batch_size=1)
        wf["3"]["inputs"]["steps"] = 2
        out = run_workflow(wf)
        images = np.asarray(out["8"][0])
        assert images.shape[0] == 1 and np.isfinite(images).all()
        assert all(os.path.exists(p) for p in out["9"][0])
