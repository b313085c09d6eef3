"""Stock-ComfyUI node-name shims (nodes_compat.py), the utility families: mask
ops, batch and latent utilities, conditioning concat, the refiner text encode,
FreeU / RescaleCFG / ModelSampling patches, the custom-sampling schedulers,
image and latent transforms. ``test_stock_nodes.py`` has the stock graph
itself and the helpers."""

import os

import numpy as np
import pytest

from tests.test_stock_nodes import _synthetic_refiner_env, _synthetic_sdxl_env


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
        # patch to bite: three levels are the shortest ladder that has both.
        cfg = sd15_config(
            model_channels=8, channel_mult=(1, 2, 4), num_res_blocks=1,
            attention_levels=(0,), transformer_depth=(1, 0, 0),
            num_heads=2, context_dim=16, norm_groups=4, dtype=jnp.float32,
        )
        m = build_unet(cfg, jax.random.key(0), sample_shape=(1, 8, 8, 4))
        x = jax.random.normal(jax.random.key(1), (1, 8, 8, 4))
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
        out_v2 = np.asarray(patched(x, t, ctx))
        assert not np.allclose(out_v2, base_out, atol=1e-4)
        (v1,) = n["FreeU"]().patch(m, b1=1.1, b2=1.2, s1=0.9, s2=0.2)
        out_v1 = np.asarray(v1(x, t, ctx))
        assert not np.allclose(out_v1, out_v2, atol=1e-4)  # v1 != v2 math
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
