"""Nearest ×2 upsample + 3×3 convolution from the low-resolution input
(``ops/basic.upsample2x_conv3x3``): the same mathematics as ``jnp.repeat`` ×2
and a SAME convolution, the two ``Upsample`` modules' parameter trees and
outputs as ``nn.Conv`` left them, and the counter that says the form engaged.
(The suite pins "highest" matmul precision: tests/conftest.py.)"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_parallelanything_tpu.models import unet, vae
from comfyui_parallelanything_tpu.ops.basic import UpsampleConv, upsample2x_conv3x3
from comfyui_parallelanything_tpu.utils.metrics import registry


def _reference(x, kernel, bias, dtype):
    """The pair as the models had it: repeat ×2, then ``nn.Conv``'s 3×3."""
    up = jnp.repeat(jnp.repeat(x.astype(dtype), 2, axis=1), 2, axis=2)
    y = jax.lax.conv_general_dilated(
        up, kernel.astype(dtype), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(dtype)


def _operands(b, h, w, c_in, c_out, with_bias, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((b, h, w, c_in)), jnp.float32)
    kernel = jnp.asarray(rng.standard_normal((3, 3, c_in, c_out)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal((c_out,)), jnp.float32)
    return x, kernel, bias if with_bias else None


# (batch, H, W, C_in, C_out, bias)
CASES = [
    (1, 4, 4, 8, 8, True),      # even sizes
    (1, 5, 7, 3, 4, True),      # odd sizes, C_in != C_out
    (1, 7, 4, 4, 3, False),     # odd x even, no bias
    (3, 6, 5, 2, 2, True),      # batch > 1
    (2, 3, 3, 5, 1, False),     # batch > 1, one output channel
    (1, 1, 1, 2, 3, True),      # a 1-pixel input: every tap but the centre pads
    (2, 1, 1, 1, 1, False),
    (1, 1, 6, 4, 4, True),      # one row
    (1, 6, 1, 4, 4, False),     # one column
    (1, 2, 2, 16, 16, True),    # borders everywhere
    (4, 8, 8, 6, 6, True),
    (1, 9, 9, 1, 5, False),
]


@pytest.mark.parametrize(
    "b,h,w,c_in,c_out,with_bias", CASES,
    ids=[f"b{c[0]}-{c[1]}x{c[2]}-{c[3]}to{c[4]}-{'bias' if c[5] else 'nobias'}"
         for c in CASES])
def test_matches_repeat_then_conv_in_float32(b, h, w, c_in, c_out, with_bias):
    x, kernel, bias = _operands(b, h, w, c_in, c_out, with_bias)
    got = upsample2x_conv3x3(x, kernel, bias, jnp.float32)
    want = _reference(x, kernel, bias, jnp.float32)
    assert got.shape == (b, 2 * h, 2 * w, c_out) and got.dtype == jnp.float32
    # Folding sums up to four taps before the product: float32 rounding only.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5 * (9 * c_in) ** 0.5)


@pytest.mark.parametrize("b,h,w,c", [(1, 8, 8, 64), (2, 5, 7, 32), (1, 1, 1, 16)])
def test_bfloat16_within_the_two_forms_rounding(b, h, w, c):
    """In bfloat16 the forms differ where they round: the reference rounds nine
    taps, the phase form the four folded ones, both accumulate in float32.
    Each lies within its operands' rounding of the float32 result."""
    x, kernel, bias = _operands(b, h, w, c, c, True, seed=1)
    kernel = kernel * (9 * c) ** -0.5
    exact = np.asarray(_reference(x, kernel, bias, jnp.float32))
    got = upsample2x_conv3x3(x.astype(jnp.bfloat16), kernel, bias, jnp.bfloat16)
    want = _reference(x.astype(jnp.bfloat16), kernel, bias, jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    # Operands rounded to 8 bits (2**-9 relative each), 9·c products of unit
    # scale summed at random sign, the result rounded once more.
    tol = 4 * 2 ** -8 * max(1.0, float(np.abs(exact).max()))
    for form in (got, want):
        assert np.abs(np.asarray(form.astype(jnp.float32)) - exact).max() < tol
    assert np.abs(np.asarray(got.astype(jnp.float32))
                  - np.asarray(want.astype(jnp.float32))).max() < tol


def test_default_dtype_is_the_inputs_and_the_kernel_may_be_any():
    """A dequantised (bfloat16) kernel folds in float32 like a resident one."""
    x, kernel, bias = _operands(1, 4, 4, 8, 8, True)
    got = upsample2x_conv3x3(x.astype(jnp.bfloat16), kernel.astype(jnp.bfloat16), bias)
    assert got.dtype == jnp.bfloat16 and got.shape == (1, 8, 8, 8)


def _plain_module(features, dtype):
    class Plain(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
            return nn.Conv(features, (3, 3), padding=1, dtype=dtype, name="conv")(x)

    return Plain()


@pytest.mark.parametrize("which", ["vae", "unet"])
def test_upsample_modules_keep_their_parameter_trees_and_outputs(which):
    """The modules' parameters are ``nn.Conv``'s — name, shapes, float32, the
    initialiser's very values — and a checkpoint's tree gives what it gave."""
    c = 8
    if which == "vae":
        module, name = vae.Upsample(vae.sd_vae_config(dtype=jnp.float32)), "conv"
    else:
        module = unet.Upsample(unet.sd15_config(dtype=jnp.float32), c)
        name = "Conv_0"
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, 5, 6, c)),
                    jnp.float32)
    params = module.init(jax.random.key(3), x)
    assert set(params["params"]) == {name}
    leaves = params["params"][name]
    assert {k: (v.shape, v.dtype) for k, v in leaves.items()} == {
        "kernel": ((3, 3, c, c), jnp.float32), "bias": ((c,), jnp.float32)}
    plain = _plain_module(c, jnp.float32)
    theirs = plain.init(jax.random.key(3), x)["params"]["conv"]
    # Same fan-in initialiser and zero bias; the key folds in the module's
    # name, so only the distribution's scale can be compared across names.
    if name == "conv":
        np.testing.assert_array_equal(np.asarray(leaves["kernel"]),
                                      np.asarray(theirs["kernel"]))
    assert abs(float(leaves["kernel"].std()) / float(theirs["kernel"].std()) - 1) < 0.2
    assert not np.asarray(leaves["bias"]).any()
    # A loaded checkpoint: any kernel and bias under the module's names.
    rng = np.random.default_rng(4)
    loaded = {"kernel": jnp.asarray(rng.standard_normal((3, 3, c, c)), jnp.float32),
              "bias": jnp.asarray(rng.standard_normal((c,)), jnp.float32)}
    got = module.apply({"params": {name: loaded}}, x)
    want = plain.apply({"params": {"conv": loaded}}, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_upsample_conv_module_is_nn_conv_under_another_name():
    x = jnp.ones((1, 2, 2, 4))
    ours = UpsampleConv(6, name="conv")
    tree = jax.eval_shape(lambda: ours.init(jax.random.key(0), x))["params"]
    assert jax.tree.map(lambda l: l.shape, tree) == {
        "kernel": (3, 3, 4, 6), "bias": (6,)}


def _phase_count():
    return registry.get("pa_upsample_conv_total", {"form": "phase"}) or 0.0


def _tiny_unet(config, **overrides):
    cfg = config(model_channels=32, norm_groups=8, context_dim=16, **overrides)
    model = unet.UNet2D(cfg)
    x = jax.ShapeDtypeStruct((2, 16, 16, cfg.in_channels), jnp.float32)
    t = jax.ShapeDtypeStruct((2,), jnp.float32)
    ctx = jax.ShapeDtypeStruct((2, 7, cfg.context_dim), jnp.float32)
    kw = {}
    if cfg.adm_in_channels:
        kw["y"] = jax.ShapeDtypeStruct((2, cfg.adm_in_channels), jnp.float32)
    return lambda: jax.eval_shape(
        lambda *a, **k: model.init(jax.random.key(0), *a, **k), x, t, ctx, **kw)


@pytest.mark.parametrize("label,moves", [("decoder", 3), ("sd15", 3), ("sdxl", 2)])
def test_counter_moves_once_a_pair_a_trace(label, moves):
    """``pa_upsample_conv_total{form="phase"}`` counts the pairs of a program
    while it is traced: 3 in the autoencoder's decoder, 3 in SD1.5's UNet, 2
    in SDXL's (tiny widths, the published depth)."""
    if label == "decoder":
        cfg = vae.sd3_vae_config(base_channels=32, norm_groups=8)
        z = jax.ShapeDtypeStruct((1, 4, 4, cfg.z_channels), jnp.float32)
        trace = lambda: jax.eval_shape(  # noqa: E731
            lambda a: vae.Decoder(cfg).init(jax.random.key(0), a), z)
    elif label == "sd15":
        trace = _tiny_unet(unet.sd15_config)
    else:
        trace = _tiny_unet(unet.sdxl_config, transformer_depth=(0, 1, 1),
                           adm_in_channels=8)
    before = _phase_count()
    trace()
    assert _phase_count() == before + moves
