"""Wan2.2's video decoder against the plain reference at tiny widths on the CPU:
the decoder as published (the temporal up-sampler's first-frame rule) and
bounded in time. One loaded decoder and one set of reference weights serve
every case of this file (``wan_twin.py`` holds the twin's files)."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from twins import _counted, _rel, twin_files  # noqa: F401 — a fixture; benchmark/ on the path
from wan_twin import CELL, _file, fresh_residency  # noqa: F401 — fixtures
import run
from yardstick import reference_sd, reference_wan, safetensors_io


@pytest.fixture(scope="module")
def vae_pair(twin_files):
    """The twin's decoder, loaded once under the twin's sizes in float32 with a
    rule of its own, and the reference's weights from the same file. A compiled
    decode stays with the decoder (one program a latent shape): a test that
    counts a trace brings a shape no other test of this file uses."""
    from comfyui_parallelanything_tpu.models import loader

    cell, _, ref_kw, _ = twin_files(CELL)
    path = _file(cell, ref_kw, 5)
    with pytest.MonkeyPatch.context() as mp:
        run.apply_program_presets(cell["config_data"], mp.setattr, jnp.float32)
        mp.setattr(loader, "residency", loader.Residency(budget_bytes=0))
        vae = loader.load_wan_vae_checkpoint(path)
    return vae, reference_sd.load_weights(safetensors_io.read(path))


@pytest.mark.parametrize("latent_frames", [1, 2, 3], ids=["1_frame", "5_frames", "9_frames"])
def test_the_video_decoder_equals_the_reference(twin_files, vae_pair, fresh_residency,
                                                latent_frames):
    """models/video_vae.py (the time-bounded program) against the decoder
    written from the published description, which walks ONE frame at a time
    with two carried frames a convolution: at 1 frame the image case, at 5 and
    9 the temporal up-samplers' first-frame rule decides every frame (the
    first passes as it is; ``time_conv`` sees zeros, not the first frame, in
    front of the second). The reference clamps to [−1, 1] as published; the
    program clamps where it maps to [0, 1]."""
    cell, (vae, w) = twin_files(CELL)[0], vae_pair
    z = jax.random.normal(jax.random.key(latent_frames), (1, latent_frames, 4, 8, 16),
                          jnp.float32)
    got = jnp.clip(vae.decode(z), -1.0, 1.0)
    want = reference_wan.wan_vae_decode("float32", w, cell["config_data"]["vae"],
                                        jnp.transpose(z, (0, 4, 1, 2, 3)))
    frames = 4 * (latent_frames - 1) + 1
    assert got.shape == (1, frames, 32, 64, 3) and want.shape == (frames, 3, 32, 64)
    for f in range(frames):
        gap = _rel(jnp.transpose(got[0, f], (2, 0, 1)), want[f])
        assert gap < 1e-4, (f, gap)


@pytest.mark.parametrize("latent_frames", [1, 2, 3], ids=["1_frame", "5_frames", "9_frames"])
def test_the_time_bounded_decode_equals_the_whole_clip_program(vae_pair, fresh_residency,
                                                               latent_frames):
    """``VideoVAE.decode`` (the first latent frame, then a scan over the
    others with every causal convolution's last two input frames as the
    carry) against ``VideoAutoencoderKL.decode`` on the whole clip with zeros
    padded in front: the same arithmetic."""
    from comfyui_parallelanything_tpu.models.video_vae import VideoAutoencoderKL

    vae, _ = vae_pair
    z = jax.random.normal(jax.random.key(7 + latent_frames),
                          (2, latent_frames, 4, 8, 16), jnp.float32)
    # one program, not an eager walk that compiles every operation on its own
    whole = jax.jit(lambda p, z: VideoAutoencoderKL(vae.cfg).apply(
        {"params": p}, z, method=VideoAutoencoderKL.decode))(vae.params, z)
    got = vae.decode(z)
    assert got.shape == whole.shape == (2, 4 * (latent_frames - 1) + 1, 32, 64, 3)
    assert float(jnp.abs(got - whole).max()) < 1e-4 * float(jnp.abs(whole).max())


def test_the_first_frame_rule_is_not_a_plain_causal_convolution(vae_pair, fresh_residency):
    """What the decoder did before: ``time_conv`` over EVERY frame with the
    first as history, one of the first frame's two outputs dropped. Under the
    published rule the clip's first pixel frame does not depend on
    ``time_conv`` at all, and equals the one-frame clip's image."""
    vae, _ = vae_pair
    z = jax.random.normal(jax.random.key(2), (1, 3, 4, 8, 16), jnp.float32)
    clip, image = vae.decode(z), vae.decode(z[:, :1])
    assert float(jnp.abs(clip[:, :1] - image).max()) < 1e-5
    broken = jax.tree.map(lambda a: a, vae.params)
    for level in ("up_3_upsample", "up_2_upsample"):
        k = broken["decoder"][level]["time_conv"]["conv"]
        k["kernel"], k["bias"] = k["kernel"] * 0.0, k["bias"] * 0.0
    other = dataclasses.replace(vae, params=broken).decode(z)
    assert float(jnp.abs(other[:, :1] - image).max()) < 1e-5
    assert float(jnp.abs(other[:, 1:] - clip[:, 1:]).max()) > 1e-2


def test_the_video_decode_is_counted_once_a_trace(vae_pair, fresh_residency):
    vae, _ = vae_pair
    before = _counted("pa_video_decode_total", frames="5", form="scan")
    z = jnp.zeros((3, 2, 4, 8, 16), jnp.float32)  # a batch no other test decodes
    vae.decode(z)
    vae.decode(z + 1.0)  # the same program: traced once
    assert _counted("pa_video_decode_total", frames="5", form="scan") == before + 1
