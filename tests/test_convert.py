"""Checkpoint conversion + LoRA baking (SURVEY §7 hard parts 2 & 5).

Strategy: synthesize a torch-layout FLUX state dict by *inverting* the converter's
layout transforms from a freshly-initialized model's params, convert it back, and
require exact structural + numerical round-trip. LoRA baking is checked against the
closed-form ``W + s·(alpha/r)·up@down``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tree_utils import flatten_tree

from comfyui_parallelanything_tpu.models.convert import (
    bake_lora,
    convert_flux_checkpoint,
    is_float8_dtype,
    linear_kernel,
    qkv_kernel,
    to_numpy,
)
from comfyui_parallelanything_tpu.models.flux import FluxConfig, build_flux


@pytest.fixture(scope="module")
def tiny():
    cfg = FluxConfig(
        in_channels=16, hidden_size=32, num_heads=2, depth=2, depth_single_blocks=2,
        context_in_dim=16, vec_in_dim=8, axes_dim=(4, 6, 6), guidance_embed=True,
        dtype=jnp.float32,
    )
    model = build_flux(cfg, jax.random.key(0), sample_shape=(1, 8, 8, 4), txt_len=8)
    return cfg, model


def _inv_dense(params, key_prefix, sd):
    sd[f"{key_prefix}.weight"] = np.asarray(params["kernel"]).T
    if "bias" in params:
        sd[f"{key_prefix}.bias"] = np.asarray(params["bias"])


def _inv_mlp_embedder(params, prefix, sd):
    _inv_dense(params["in_layer"], f"{prefix}.in_layer", sd)
    _inv_dense(params["out_layer"], f"{prefix}.out_layer", sd)


def _torch_layout_sd(cfg: FluxConfig, params) -> dict:
    """Model params → official FLUX checkpoint layout (the converter's inverse)."""
    sd: dict = {}
    # The file orders a token's patch features (c, ph, pw), the model
    # (ph, pw, c): the converter permutes img_in's inputs and the final
    # projection's outputs, and this is its inverse.
    pp = cfg.patch_size ** 2
    ch = cfg.in_channels // pp

    def file_order(a, axis):
        a = np.asarray(a)
        shape = a.shape
        a = a.reshape(shape[:axis] + (pp, ch) + shape[axis + 1:])
        return np.swapaxes(a, axis, axis + 1).reshape(shape)

    params = {**params,
              "img_in": {**params["img_in"],
                         "kernel": file_order(params["img_in"]["kernel"], 0)},
              "final_proj": {k: file_order(v, np.ndim(v) - 1)
                             for k, v in params["final_proj"].items()}}
    _inv_dense(params["img_in"], "img_in", sd)
    _inv_dense(params["txt_in"], "txt_in", sd)
    _inv_mlp_embedder(params["time_in"], "time_in", sd)
    _inv_mlp_embedder(params["vector_in"], "vector_in", sd)
    if cfg.guidance_embed:
        _inv_mlp_embedder(params["guidance_in"], "guidance_in", sd)
    for i in range(cfg.depth):
        blk = params[f"double_blocks_{i}"]
        t = f"double_blocks.{i}"
        for s in ("img", "txt"):
            _inv_dense(blk[f"{s}_mod"]["lin"], f"{t}.{s}_mod.lin", sd)
            k = np.asarray(blk[f"{s}_attn_qkv"]["kernel"])  # (in, 3, H, D)
            sd[f"{t}.{s}_attn.qkv.weight"] = (
                k.transpose(1, 2, 3, 0).reshape(-1, k.shape[0])
            )
            sd[f"{t}.{s}_attn.qkv.bias"] = np.asarray(
                blk[f"{s}_attn_qkv"]["bias"]
            ).reshape(-1)
            sd[f"{t}.{s}_attn.norm.query_norm.scale"] = np.asarray(
                blk[f"{s}_attn_norm"]["query_norm"]
            )
            sd[f"{t}.{s}_attn.norm.key_norm.scale"] = np.asarray(
                blk[f"{s}_attn_norm"]["key_norm"]
            )
            _inv_dense(blk[f"{s}_attn_proj"], f"{t}.{s}_attn.proj", sd)
            _inv_dense(blk[f"{s}_mlp_in"], f"{t}.{s}_mlp.0", sd)
            _inv_dense(blk[f"{s}_mlp_out"], f"{t}.{s}_mlp.2", sd)
    for i in range(cfg.depth_single_blocks):
        blk = params[f"single_blocks_{i}"]
        t = f"single_blocks.{i}"
        _inv_dense(blk["modulation"]["lin"], f"{t}.modulation.lin", sd)
        _inv_dense(blk["linear1"], f"{t}.linear1", sd)
        _inv_dense(blk["linear2"], f"{t}.linear2", sd)
        sd[f"{t}.norm.query_norm.scale"] = np.asarray(blk["norm"]["query_norm"])
        sd[f"{t}.norm.key_norm.scale"] = np.asarray(blk["norm"]["key_norm"])
    _inv_dense(params["final_mod"], "final_layer.adaLN_modulation.1", sd)
    _inv_dense(params["final_proj"], "final_layer.linear", sd)
    return sd


def _tree_paths(tree, prefix=()):
    if isinstance(tree, dict):
        out = []
        for k, v in tree.items():
            out.extend(_tree_paths(v, prefix + (k,)))
        return out
    return [(prefix, np.asarray(tree).shape)]


class TestFluxRoundTrip:
    def test_structure_and_values(self, tiny):
        cfg, model = tiny
        sd = _torch_layout_sd(cfg, model.params)
        got = convert_flux_checkpoint(sd, cfg)
        assert sorted(_tree_paths(got)) == sorted(_tree_paths(model.params))
        flat_got = dict(flatten_tree(got))
        flat_want = dict(flatten_tree(model.params))
        for k in flat_want:
            np.testing.assert_allclose(
                flat_got[k], np.asarray(flat_want[k]), rtol=1e-6, atol=1e-6,
                err_msg=str(k),
            )

    def test_converted_params_run_forward(self, tiny):
        # Both sides run through the SAME jitted program: converted params must be
        # bitwise substitutes for the originals. (Comparing a jitted forward against
        # an eager one instead would measure XLA fusion noise amplified through the
        # random-init blocks — ~2.6e-3 on this tiny config — not converter fidelity.)
        cfg, model = tiny
        sd = _torch_layout_sd(cfg, model.params)
        params = convert_flux_checkpoint(sd, cfg)
        x = jax.random.normal(jax.random.key(1), (1, 8, 8, 4), jnp.float32)
        ctx = jax.random.normal(jax.random.key(2), (1, 8, 16), jnp.float32)
        y = jax.random.normal(jax.random.key(3), (1, 8), jnp.float32)
        f = jax.jit(model.apply)
        want = f(model.params, x, jnp.array([0.5]), ctx, y=y)
        got = f(params, x, jnp.array([0.5]), ctx, y=y)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)



class TestLoRABaking:
    def test_kohya_style_closed_form(self):
        rng = np.random.default_rng(0)
        w = rng.standard_normal((8, 6)).astype(np.float32)
        down = rng.standard_normal((2, 6)).astype(np.float32)  # (r, in)
        up = rng.standard_normal((8, 2)).astype(np.float32)  # (out, r)
        sd = {"blocks.0.proj.weight": w}
        lora = {
            "blocks.0.proj.lora_down.weight": down,
            "blocks.0.proj.lora_up.weight": up,
            "blocks.0.proj.alpha": np.float32(4.0),
        }
        merged = bake_lora(sd, lora, strength=0.5)
        want = w + 0.5 * (4.0 / 2.0) * (up @ down)
        np.testing.assert_allclose(merged["blocks.0.proj.weight"], want, rtol=1e-6)

    def test_diffusers_style_and_underscore_matching(self):
        rng = np.random.default_rng(1)
        w = rng.standard_normal((4, 4)).astype(np.float32)
        down = rng.standard_normal((1, 4)).astype(np.float32)
        up = rng.standard_normal((4, 1)).astype(np.float32)
        sd = {"double_blocks.0.img_attn.proj.weight": w}
        lora = {
            "lora_unet_double_blocks_0_img_attn_proj.lora_A.weight": down,
            "lora_unet_double_blocks_0_img_attn_proj.lora_B.weight": up,
        }
        merged = bake_lora(sd, lora)
        want = w + up @ down  # no alpha → scale 1
        np.testing.assert_allclose(
            merged["double_blocks.0.img_attn.proj.weight"], want, rtol=1e-6
        )

    def test_unmatched_lora_skipped(self):
        sd = {"a.weight": np.zeros((2, 2), np.float32)}
        lora = {
            "nonexistent.lora_down.weight": np.zeros((1, 2), np.float32),
            "nonexistent.lora_up.weight": np.zeros((2, 1), np.float32),
        }
        merged = bake_lora(sd, lora)
        np.testing.assert_array_equal(merged["a.weight"], sd["a.weight"])


class TestDtypeHandling:
    def test_fp8_names_detected(self):
        assert is_float8_dtype("torch.float8_e4m3fn")
        assert is_float8_dtype("float8_e5m2")
        assert not is_float8_dtype("torch.float16")

    def test_torch_bf16_and_fp8_upcast(self):
        torch = pytest.importorskip("torch")
        t = torch.randn(3, 3, dtype=torch.bfloat16)
        out = to_numpy(t)
        assert out.dtype == np.float32
        if hasattr(torch, "float8_e4m3fn"):
            t8 = torch.randn(3, 3).to(torch.float8_e4m3fn)
            out8 = to_numpy(t8)
            assert out8.dtype == np.float32

    def test_layout_transforms(self):
        w = np.arange(12, dtype=np.float32).reshape(4, 3)
        assert linear_kernel(w).shape == (3, 4)
        k = qkv_kernel(np.zeros((3 * 2 * 4, 5), np.float32), heads=2, head_dim=4)
        assert k.shape == (5, 3, 2, 4)
