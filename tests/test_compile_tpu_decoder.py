"""The autoencoder's decode program at published widths and 1 x 1024², compiled
by the TPU's own compiler for the described ``v5e:2x2`` chip of
``test_tpu_compile.py``. The longest test there is (some 350 s: a
published-width compile, kept as it is), so it has a file to itself that
``--dist loadfile`` hands out early."""

import jax
import jax.numpy as jnp

from tests.test_compile_tpu_blocks import _compile_block
from tests.test_tpu_compile import one_chip, topo  # noqa: F401 — fixtures

# The autoencoder's decode program at 1 x 1024² (every 1024² cell's, once a
# request), by XLA's own analyses of the compiled program. Before PR 38 its
# three upsamplers were ``jax.image.resize`` (two gather fusions a stage, a
# copy, a pad, a copy and a slice) and a 3x3 convolution at the HIGH
# resolution: 10.16 TFLOP, 1,361,243,136 B of temporaries.
DECODER_PARENT_TEMP_BYTES = 1_361_243_136


def test_decoder_upsamplers_run_at_the_low_resolution(monkeypatch, one_chip):
    """``ops/basic.upsample2x_conv3x3`` in the decoder's program: no gather is
    left, the three pairs cost 16 tap-products a source pixel where they cost
    36 (-1.55 TFLOP), and the program's temporaries did not grow —
    ``flux-schnell`` runs at 15.1-15.3 GB of the chip's 16."""
    from comfyui_parallelanything_tpu.models import vae

    compiled = _compile_block(
        monkeypatch, one_chip, vae.Decoder(vae.sd3_vae_config()),
        jax.ShapeDtypeStruct((1, 128, 128, 16), jnp.float32))
    text = compiled.as_text()
    assert "tpu_custom_call" in text  # the mid-block attention's flash kernel
    assert "gather" not in text
    assert compiled.cost_analysis()["flops"] <= 8.8e12  # parent 10.16e12
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= DECODER_PARENT_TEMP_BYTES
