"""WAN2.2 A14B timestep-boundary expert switching: routing correctness, sampler
integration (the host-loop samplers make the switch concrete per step), and the
dual-expert WanVideoPipeline path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_parallelanything_tpu.models import (
    TimestepExpertSwitch,
    WAN22_T2V_BOUNDARY,
)
from comfyui_parallelanything_tpu.sampling.runner import run_sampler


def _tagged_model(tag: float):
    """Velocity model returning a constant, so which expert ran is readable off
    the integrated output."""

    def f(x, t, context=None, **kw):
        return jnp.full_like(x, tag)

    return f


class TestSwitch:
    def test_routes_by_boundary(self):
        sw = TimestepExpertSwitch(_tagged_model(1.0), _tagged_model(-1.0), 0.5)
        x = jnp.zeros((1, 4))
        hi = sw(x, jnp.array([0.9]))
        lo = sw(x, jnp.array([0.1]))
        assert float(hi[0, 0]) == 1.0 and float(lo[0, 0]) == -1.0

    def test_boundary_inclusive_high(self):
        sw = TimestepExpertSwitch(_tagged_model(1.0), _tagged_model(-1.0), 0.5)
        out = sw(jnp.zeros((1, 4)), jnp.array([0.5]))
        assert float(out[0, 0]) == 1.0

    def test_default_boundary_is_wan22_t2v(self):
        sw = TimestepExpertSwitch(None, None)
        assert sw.boundary == WAN22_T2V_BOUNDARY

    def test_flow_sampler_uses_both_experts(self):
        """With boundary 0.5 and a 4-step flow schedule, early steps integrate
        +1 velocity and late steps -1 — both experts must contribute."""
        sw = TimestepExpertSwitch(_tagged_model(1.0), _tagged_model(-1.0), 0.5)
        noise = jnp.zeros((1, 4, 4, 4))
        out = run_sampler(sw, noise, None, sampler="flow_euler", steps=4)
        only_high = run_sampler(
            _tagged_model(1.0), noise, None, sampler="flow_euler", steps=4
        )
        only_low = run_sampler(
            _tagged_model(-1.0), noise, None, sampler="flow_euler", steps=4
        )
        # dt < 0 integrating t: 1 → 0, so a +1-velocity (high) run lands LOWER.
        v = float(out[0, 0, 0, 0])
        assert float(only_high[0, 0, 0, 0]) < v < float(only_low[0, 0, 0, 0])

    def test_model_config_comes_from_high_expert(self):
        class Cfg:
            patch_size = (1, 2, 2)

        class M:
            config = Cfg()

            def __call__(self, *a, **k):
                return None

        sw = TimestepExpertSwitch(M(), _tagged_model(0.0))
        assert sw.model_config.patch_size == (1, 2, 2)

    def test_cleanup_reaches_both(self):
        calls = []

        class M:
            def __init__(self, tag):
                self.tag = tag

            def cleanup(self):
                calls.append(self.tag)

        TimestepExpertSwitch(M("hi"), M("lo")).cleanup()
        assert calls == ["hi", "lo"]


class TestDualExpertPipeline:
    def test_wan22_dual_expert_t2v(self):
        from comfyui_parallelanything_tpu.models import (
            T5Config,
            VideoVAEConfig,
            WanConfig,
            build_t5_encoder,
            build_video_vae,
            build_wan,
        )
        from comfyui_parallelanything_tpu.pipelines import WanVideoPipeline
        from test_tokenizer import _tiny_tokenizer

        ZC = 4
        wcfg = WanConfig(
            in_channels=ZC, out_channels=ZC, hidden_size=48, ffn_dim=96,
            num_heads=4, depth=2, text_dim=32, freq_dim=16, dtype=jnp.float32,
        )
        vcfg = VideoVAEConfig(
            base_channels=8, channel_mult=(1, 2, 2), num_res_blocks=1,
            temporal_downsample=(False, True), z_channels=ZC,
            latent_mean=(0.0,) * ZC, latent_std=(1.0,) * ZC, dtype=jnp.float32,
        )
        tcfg = T5Config(
            vocab_size=64, d_model=32, d_kv=8, d_ff=64, num_layers=2,
            num_heads=4, dtype=jnp.float32,
        )
        hi = build_wan(wcfg, jax.random.key(0), sample_shape=(1, 2, 4, 4, ZC), txt_len=6)
        lo = build_wan(wcfg, jax.random.key(9), sample_shape=(1, 2, 4, 4, ZC), txt_len=6)
        pipe = WanVideoPipeline(
            dit=hi,
            vae=build_video_vae(vcfg, jax.random.key(1), sample_thw=(3, 8, 8)),
            t5=build_t5_encoder(tcfg, jax.random.key(2), sample_len=8),
            t5_tokenizer=_tiny_tokenizer(),
            dit_low_noise=lo,
            boundary=0.5,
        )
        # shift=1.0 keeps the 3 model calls at t = 1, 2/3, 1/3 so boundary
        # 0.5 genuinely splits them (the default shift 5 piles all three above
        # 0.7 and the low expert would never fire).
        video = pipe(
            "hello", steps=3, cfg_scale=1.0, height=16, width=16, frames=5,
            shift=1.0,
        )
        assert video.shape == (1, 5, 16, 16, 3)
        assert np.isfinite(np.asarray(video)).all()
        # Single-expert run differs — the low-noise expert really participates.
        single = WanVideoPipeline(
            dit=hi, vae=pipe.vae, t5=pipe.t5, t5_tokenizer=pipe.t5_tokenizer,
        )("hello", steps=3, cfg_scale=1.0, height=16, width=16, frames=5, shift=1.0)
        assert not np.allclose(np.asarray(video), np.asarray(single))


class TestVideo2Video:
    def test_init_video_shifts_output(self, monkeypatch):
        from comfyui_parallelanything_tpu.models import (
            T5Config, VideoVAEConfig, WanConfig, build_t5_encoder,
            build_video_vae, build_wan,
        )
        from comfyui_parallelanything_tpu.pipelines import WanVideoPipeline
        from test_tokenizer import _tiny_tokenizer

        ZC = 4
        pipe = WanVideoPipeline(
            dit=build_wan(
                WanConfig(in_channels=ZC, out_channels=ZC, hidden_size=48,
                          ffn_dim=96, num_heads=4, depth=1, text_dim=32,
                          freq_dim=16, dtype=jnp.float32),
                jax.random.key(0), sample_shape=(1, 2, 4, 4, ZC), txt_len=6,
            ),
            vae=build_video_vae(
                VideoVAEConfig(base_channels=8, channel_mult=(1, 2, 2),
                               num_res_blocks=1, temporal_downsample=(False, True),
                               z_channels=ZC, latent_mean=(0.0,) * ZC,
                               latent_std=(1.0,) * ZC, dtype=jnp.float32),
                jax.random.key(1), sample_thw=(3, 8, 8),
            ),
            t5=build_t5_encoder(
                T5Config(vocab_size=64, d_model=32, d_kv=8, d_ff=64,
                         num_layers=2, num_heads=4, dtype=jnp.float32),
                jax.random.key(2), sample_len=8,
            ),
            t5_tokenizer=_tiny_tokenizer(),
        )
        init = jnp.full((1, 5, 16, 16, 3), 0.5)
        kw = dict(steps=2, cfg_scale=1.0, height=16, width=16, frames=5, shift=1.0)
        # The init is preserved in the SAMPLED LATENTS, where the property
        # lives: a flow sampler at strength d starts from (1 - d) z0 + d noise
        # and moves it by at most d v. Through the random-weight decoder (RMS
        # norms without a scale to hold, random kernels) the pixel distance to
        # the init's own decode is a coin flip over the noise key (0.1847
        # against 0.1872 at key 3, 0.18968 against 0.18939 at key 5), so the
        # latents are taken where the pipeline hands them to the decoder.
        from comfyui_parallelanything_tpu.models import vae as vae_mod
        from comfyui_parallelanything_tpu.models.vae import images_to_vae_input

        sampled = []
        real_decode = vae_mod.decode_maybe_tiled

        def keep(vae, z, tile=0):
            sampled.append(np.asarray(z, np.float32))
            return real_decode(vae, z, tile)

        monkeypatch.setattr(vae_mod, "decode_maybe_tiled", keep)
        z0 = np.asarray(pipe.vae.encode(images_to_vae_input(init)), np.float32)

        def distance(**more):
            video = np.asarray(pipe("hello", **kw, **more))
            assert video.shape == (1, 5, 16, 16, 3)
            assert np.isfinite(video).all()
            return float(np.abs(sampled.pop() - z0).mean())

        for key in (3, 5, 11):
            rng = jax.random.key(key)
            full = distance(rng=rng)
            weak = distance(rng=rng, init_video=init, denoise=0.25)
            faint = distance(rng=rng, init_video=init, denoise=0.05)
            # Nearer the encoded init the lower the strength, by the strength's
            # own factor (a quarter and a twentieth of the full run's distance,
            # with room for what the denoiser's velocity adds): an ignored
            # `denoise`, a wrong noising scale or an init noised at the wrong
            # sigma all fail here.
            assert faint < weak < full, (key, faint, weak, full)
            assert weak < 0.35 * full, (key, weak, full)
            assert faint < 0.08 * full, (key, faint, full)

    def test_denoise_without_init_video_rejected(self):
        from comfyui_parallelanything_tpu.pipelines import _encode_init

        with pytest.raises(ValueError, match="init_video"):
            _encode_init(None, None, 0.5, 1, (5, 16, 16), what="init_video")
