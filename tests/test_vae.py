"""AutoencoderKL: shapes, converter round-trip, tiled decode, loader sniffing.

Same strategy as test_convert.py: synthesize an ldm-layout state dict by inverting
the converter's layout transforms from freshly-initialized params, convert it back,
and require a bitwise round-trip (the converter only relays/transposes weights)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tree_utils import flatten_tree

from comfyui_parallelanything_tpu.models.convert_vae import (
    convert_vae_checkpoint,
    strip_vae_prefix,
)
from comfyui_parallelanything_tpu.models.loader import load_vae_checkpoint
from comfyui_parallelanything_tpu.models.vae import (
    VAEConfig,
    build_vae,
    flux_vae_config,
    sd_vae_config,
    sdxl_vae_config,
)

TINY = VAEConfig(
    z_channels=4,
    base_channels=32,
    channel_mult=(1, 2),
    num_res_blocks=1,
    norm_groups=8,
    dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def tiny_vae():
    return build_vae(TINY, jax.random.key(0), sample_hw=16)


# Params → ldm checkpoint layout (the converter's inverse) is chip_smoke.py's
# (it writes the checkpoint the chip smoke serves): the round-trip tests below
# pin it against the converter.
from chip_smoke import ldm_vae_state_dict as _ldm_layout_sd  # noqa: E402


class TestShapes:
    def test_encode_decode_shapes(self, tiny_vae):
        f = tiny_vae.spatial_factor
        assert f == 2
        x = jax.random.normal(jax.random.key(1), (2, 16, 16, 3), jnp.float32)
        z = tiny_vae.encode(x)
        assert z.shape == (2, 16 // f, 16 // f, TINY.z_channels)
        img = tiny_vae.decode(z)
        assert img.shape == x.shape

    def test_encode_sampling_differs_from_mean(self, tiny_vae):
        x = jax.random.normal(jax.random.key(1), (1, 16, 16, 3), jnp.float32)
        z_mean = tiny_vae.encode(x)
        z_smp = tiny_vae.encode(x, rng=jax.random.key(2))
        assert not np.allclose(np.asarray(z_mean), np.asarray(z_smp))

    def test_family_config_constants(self):
        assert sd_vae_config().scaling_factor == pytest.approx(0.18215)
        assert sdxl_vae_config().scaling_factor == pytest.approx(0.13025)
        assert flux_vae_config().z_channels == 16
        assert not flux_vae_config().use_quant_conv

    def test_scale_shift_applied_against_closed_form(self, tiny_vae):
        """Independent check of the latent conventions (a swapped inversion order in
        decode would cancel out in any encode→decode round-trip test):

        - encode (no rng) must equal (posterior_mean - shift) * scale exactly;
        - decode under (scale, shift) must equal the identity-convention decode of
          z / scale + shift, with weights held fixed.
        """
        import dataclasses

        from comfyui_parallelanything_tpu.models.vae import VAE, AutoencoderKL

        cfg = dataclasses.replace(TINY, scaling_factor=0.37, shift_factor=0.21)
        vae = VAE(cfg=cfg, params=tiny_vae.params)
        ident = VAE(
            cfg=dataclasses.replace(cfg, scaling_factor=1.0, shift_factor=0.0),
            params=tiny_vae.params,
        )
        x = jax.random.normal(jax.random.key(8), (1, 16, 16, 3), jnp.float32)
        module = AutoencoderKL(cfg)
        mean, _ = module.apply(
            {"params": vae.params}, x, method=AutoencoderKL.moments
        )
        np.testing.assert_allclose(
            np.asarray(vae.encode(x)),
            (np.asarray(mean) - cfg.shift_factor) * cfg.scaling_factor,
            rtol=1e-6,
            atol=1e-6,
        )
        z = jax.random.normal(jax.random.key(9), (1, 8, 8, 4), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(vae.decode(z)),
            np.asarray(ident.decode(z / cfg.scaling_factor + cfg.shift_factor)),
            rtol=1e-5,
            atol=1e-5,
        )


class TestConverterRoundTrip:
    def test_bitwise_roundtrip(self, tiny_vae):
        sd = _ldm_layout_sd(TINY, tiny_vae.params)
        got = convert_vae_checkpoint(sd, TINY)
        flat_got = dict(flatten_tree(got))
        flat_want = dict(flatten_tree(tiny_vae.params))
        assert sorted(flat_got) == sorted(flat_want)
        for k in flat_want:
            np.testing.assert_array_equal(flat_got[k], flat_want[k], err_msg=str(k))

    def test_rank2_attention_projections(self, tiny_vae):
        # diffusers-style exports store attn q/k/v/proj_out as rank-2 linears.
        sd = _ldm_layout_sd(TINY, tiny_vae.params)
        for t in ("encoder.mid.attn_1", "decoder.mid.attn_1"):
            for k in ("q", "k", "v", "proj_out"):
                w = sd[f"{t}.{k}.weight"]
                sd[f"{t}.{k}.weight"] = w[:, :, 0, 0]
        got = convert_vae_checkpoint(sd, TINY)
        np.testing.assert_array_equal(
            np.asarray(got["encoder"]["mid_attn_1"]["q"]["kernel"]),
            np.asarray(tiny_vae.params["encoder"]["mid_attn_1"]["q"]["kernel"]),
        )

    def test_prefix_stripping(self, tiny_vae):
        sd = _ldm_layout_sd(TINY, tiny_vae.params)
        prefixed = {f"first_stage_model.{k}": v for k, v in sd.items()}
        # Combined checkpoints carry non-VAE keys too — they must be ignored.
        prefixed["model.diffusion_model.out.0.weight"] = np.zeros(4, np.float32)
        assert sorted(strip_vae_prefix(prefixed)) == sorted(sd)

    def test_unconsumed_keys_rejected(self, tiny_vae):
        sd = _ldm_layout_sd(TINY, tiny_vae.params)
        sd["encoder.down.7.block.0.conv1.weight"] = np.zeros((4, 4, 3, 3), np.float32)
        with pytest.raises(ValueError, match="unconverted"):
            convert_vae_checkpoint(sd, TINY)

    def test_in_range_attn_variant_rejected(self, tiny_vae):
        # kl-f16-style layouts carry encoder.down.{l}.attn.{i}.* — indices are
        # in-range, so only consumed-key tracking catches the mismatch.
        sd = _ldm_layout_sd(TINY, tiny_vae.params)
        sd["encoder.down.0.attn.0.q.weight"] = np.zeros((32, 32, 1, 1), np.float32)
        with pytest.raises(ValueError, match="unconverted"):
            convert_vae_checkpoint(sd, TINY)


class TestTiledDecode:
    def test_matches_full_decode_in_interior(self, tiny_vae):
        z = jax.random.normal(jax.random.key(3), (1, 24, 24, 4), jnp.float32)
        full = np.asarray(tiny_vae.decode(z), np.float32)
        tiled = np.asarray(tiny_vae.decode_tiled(z, tile=16, overlap=8), np.float32)
        assert tiled.shape == full.shape
        # Conv receptive fields cross tile edges, so exact equality only holds
        # away from seams — and at this toy geometry (16-px tiles, 8-px
        # overlap, a decoder receptive field spanning most of a tile) the seam
        # halo covers nearly every pixel, leaving a deterministic ~5% mean
        # deviation. Bound it relative to the signal scale so the check
        # survives decoder-depth tweaks while still catching a broken blend
        # (an unblended hard seam is several times this).
        assert np.mean(np.abs(tiled - full)) < 0.1 * np.mean(np.abs(full))

    def test_non_square_and_single_axis_tiling(self, tiny_vae):
        z = jax.random.normal(jax.random.key(4), (1, 8, 40, 4), jnp.float32)
        out = tiny_vae.decode_tiled(z, tile=16, overlap=4)
        assert out.shape == (1, 16, 80, 3)

    def test_small_latent_short_circuits(self, tiny_vae):
        z = jax.random.normal(jax.random.key(5), (1, 8, 8, 4), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(tiny_vae.decode_tiled(z, tile=16)),
            np.asarray(tiny_vae.decode(z)),
            rtol=1e-6,
            atol=1e-6,
        )

    def test_invalid_overlap_rejected(self, tiny_vae):
        z = jnp.zeros((1, 40, 40, 4), jnp.float32)
        with pytest.raises(ValueError, match="overlap"):
            tiny_vae.decode_tiled(z, tile=16, overlap=16)

    def test_zero_overlap_valid(self, tiny_vae):
        z = jax.random.normal(jax.random.key(6), (1, 24, 24, 4), jnp.float32)
        out = tiny_vae.decode_tiled(z, tile=16, overlap=0)
        assert out.shape == (1, 48, 48, 3)
        assert np.isfinite(np.asarray(out)).all()


class TestLoader:
    def test_load_from_state_dict_with_sniffed_config(self, tiny_vae):
        sd = _ldm_layout_sd(TINY, tiny_vae.params)
        # Sniffing picks sd_vae_config for 4-channel latents; TINY differs from the
        # full-size config, so pass cfg explicitly and check the sniff separately.
        vae = load_vae_checkpoint(sd, cfg=TINY)
        x = jax.random.normal(jax.random.key(7), (1, 16, 16, 3), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(vae.decode(vae.encode(x))),
            np.asarray(tiny_vae.decode(tiny_vae.encode(x))),
            rtol=1e-6,
            atol=1e-6,
        )

    def test_sniff_flux_vs_sd(self):
        from comfyui_parallelanything_tpu.models.loader import sniff_vae_config

        sd4 = {"decoder.conv_in.weight": np.zeros((64, 4, 3, 3), np.float32)}
        sd16 = {"decoder.conv_in.weight": np.zeros((64, 16, 3, 3), np.float32)}
        assert sniff_vae_config(sd4).z_channels == 4
        assert sniff_vae_config(sd4).use_quant_conv
        assert sniff_vae_config(sd16).z_channels == 16
        assert not sniff_vae_config(sd16).use_quant_conv
        # Prefixed (full ComfyUI checkpoint) layout sniffs too.
        pre = {"first_stage_model.decoder.conv_in.weight": sd16[
            "decoder.conv_in.weight"
        ]}
        assert sniff_vae_config(pre).z_channels == 16
        with pytest.raises(KeyError, match="AutoencoderKL"):
            sniff_vae_config({"not_a_vae.weight": np.zeros(1, np.float32)})


class TestTiledEncode:
    def test_matches_full_encode(self, tiny_vae):
        x = jax.random.uniform(jax.random.key(7), (1, 80, 80, 3)) * 2 - 1
        full = np.asarray(tiny_vae.encode(x), np.float32)
        tiled = np.asarray(tiny_vae.encode_tiled(x, tile=48, overlap=16), np.float32)
        assert tiled.shape == full.shape
        assert np.mean(np.abs(tiled - full)) < 2e-2

    def test_small_input_short_circuits(self, tiny_vae):
        x = jax.random.uniform(jax.random.key(8), (1, 16, 16, 3))
        np.testing.assert_array_equal(
            np.asarray(tiny_vae.encode_tiled(x, tile=32)),
            np.asarray(tiny_vae.encode(x)),
        )

    def test_unaligned_tile_rejected(self, tiny_vae):
        with pytest.raises(ValueError, match="multiples"):
            tiny_vae.encode_tiled(jnp.zeros((1, 64, 64, 3)), tile=31, overlap=8)

    def test_encode_maybe_tiled_aligns_overlap(self, tiny_vae):
        """Any factor-aligned tile size works — the helper floors the derived
        overlap to the VAE's alignment."""
        from comfyui_parallelanything_tpu.models.vae import encode_maybe_tiled

        x = jax.random.uniform(jax.random.key(9), (1, 72, 72, 3))
        out = encode_maybe_tiled(tiny_vae, x, 52)  # 52//4=13 → floored to 12
        assert out.shape == (1, 36, 36, 4)
