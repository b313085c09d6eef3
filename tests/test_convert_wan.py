"""WAN converter: official-layout round-trip + same-program forward substitution.

Strategy mirrors test_convert.py: synthesize an official-layout state dict by
inverting the converter's transforms from freshly-initialized params, convert it
back, require bitwise identity, and run both param sets through one jitted
forward."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tree_utils import flatten_tree

from comfyui_parallelanything_tpu.models.convert_wan import convert_wan_checkpoint
from comfyui_parallelanything_tpu.models.loader import load_wan_checkpoint
from comfyui_parallelanything_tpu.models.wan import WanConfig, build_wan

TINY = WanConfig(
    in_channels=4,
    out_channels=4,
    hidden_size=48,
    ffn_dim=96,
    num_heads=4,
    depth=2,
    text_dim=32,
    freq_dim=16,
    dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def tiny_wan():
    return build_wan(TINY, jax.random.key(0), sample_shape=(1, 2, 4, 4, 4), txt_len=6)


def _inv_dense(p, key, sd):
    sd[f"{key}.weight"] = np.asarray(p["kernel"]).T
    if "bias" in p:
        sd[f"{key}.bias"] = np.asarray(p["bias"])


def _inv_ln(p, key, sd):
    sd[f"{key}.weight"] = np.asarray(p["scale"])
    sd[f"{key}.bias"] = np.asarray(p["bias"])


def _official_layout_sd(cfg: WanConfig, params) -> dict:
    sd: dict = {}
    if cfg.img_dim is not None:
        _inv_ln(params["img_ln_in"], "img_emb.proj.0", sd)
        _inv_dense(params["img_in"], "img_emb.proj.1", sd)
        _inv_dense(params["img_hidden"], "img_emb.proj.3", sd)
        _inv_ln(params["img_ln_out"], "img_emb.proj.4", sd)
    pt, ph, pw = cfg.patch_size
    k = np.asarray(params["patch_embedding"]["kernel"])  # (pt·ph·pw·C, O)
    sd["patch_embedding.weight"] = (
        k.reshape(pt, ph, pw, cfg.in_channels, -1).transpose(4, 3, 0, 1, 2)
    )
    sd["patch_embedding.bias"] = np.asarray(params["patch_embedding"]["bias"])
    _inv_dense(params["text_in"], "text_embedding.0", sd)
    _inv_dense(params["text_hidden"], "text_embedding.2", sd)
    _inv_dense(params["time_in"], "time_embedding.0", sd)
    _inv_dense(params["time_hidden"], "time_embedding.2", sd)
    _inv_dense(params["time_projection"], "time_projection.1", sd)
    _inv_dense(params["head_proj"], "head.head", sd)
    sd["head.modulation"] = np.asarray(params["head_modulation"]["bias"])
    for i in range(cfg.depth):
        blk = params[f"blocks_{i}"]
        t = f"blocks.{i}"
        for ours, theirs in (("self", "self_attn"), ("cross", "cross_attn")):
            for proj in "qkvo":
                _inv_dense(blk[f"{ours}_{proj}"], f"{t}.{theirs}.{proj}", sd)
            for nrm in "qk":
                sd[f"{t}.{theirs}.norm_{nrm}.weight"] = np.asarray(
                    blk[f"{ours}_{nrm}_norm"]["scale"]
                )
        sd[f"{t}.norm3.weight"] = np.asarray(blk["norm3"]["scale"])
        sd[f"{t}.norm3.bias"] = np.asarray(blk["norm3"]["bias"])
        _inv_dense(blk["ffn_in"], f"{t}.ffn.0", sd)
        _inv_dense(blk["ffn_out"], f"{t}.ffn.2", sd)
        sd[f"{t}.modulation"] = np.asarray(blk["modulation"])
        if cfg.img_dim is not None:
            _inv_dense(blk["cross_k_img"], f"{t}.cross_attn.k_img", sd)
            _inv_dense(blk["cross_v_img"], f"{t}.cross_attn.v_img", sd)
            sd[f"{t}.cross_attn.norm_k_img.weight"] = np.asarray(
                blk["cross_k_img_norm"]["scale"]
            )
    return sd


class TestWanRoundTrip:
    def test_bitwise_roundtrip(self, tiny_wan):
        sd = _official_layout_sd(TINY, tiny_wan.params)
        got = convert_wan_checkpoint(sd, TINY)
        fg = dict(flatten_tree(got))
        fw = dict(flatten_tree(tiny_wan.params))
        assert sorted(fg) == sorted(fw)
        for k in fw:
            np.testing.assert_array_equal(fg[k], fw[k], err_msg=str(k))

    def test_converted_params_run_forward(self, tiny_wan):
        sd = _official_layout_sd(TINY, tiny_wan.params)
        params = convert_wan_checkpoint(sd, TINY)
        x = jax.random.normal(jax.random.key(1), (1, 2, 4, 4, 4), jnp.float32)
        t = jnp.array([0.5])
        ctx = jax.random.normal(jax.random.key(2), (1, 6, 32), jnp.float32)
        f = jax.jit(tiny_wan.apply)
        want = f(tiny_wan.params, x, t, ctx)
        got = f(params, x, t, ctx)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_loader_default_path(self, tiny_wan):
        sd = _official_layout_sd(TINY, tiny_wan.params)
        model = load_wan_checkpoint(sd, TINY)
        x = jnp.zeros((1, 2, 4, 4, 4), jnp.float32)
        ctx = jnp.zeros((1, 6, 32), jnp.float32)
        out = jax.jit(model.apply)(model.params, x, jnp.array([0.1]), ctx)
        assert out.shape == (1, 2, 4, 4, 4)

    def test_i2v_branch_keys_ignored(self, tiny_wan):
        sd = _official_layout_sd(TINY, tiny_wan.params)
        sd["img_emb.proj.0.weight"] = np.zeros((8, 8), np.float32)
        got = convert_wan_checkpoint(sd, TINY)  # no error, branch ignored
        assert "img_emb" not in got


TINY_I2V = WanConfig(
    in_channels=9,  # 4 latent + 4 mask + 1-ch cond stand-in (shape-only tiny)
    out_channels=4,
    hidden_size=48,
    ffn_dim=96,
    num_heads=4,
    depth=2,
    text_dim=32,
    freq_dim=16,
    img_dim=24,
    dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def tiny_wan_i2v():
    return build_wan(
        TINY_I2V, jax.random.key(3), sample_shape=(1, 2, 4, 4, 9), txt_len=6
    )


class TestWanI2VClipBranch:
    """WAN2.1-style i2v: img_emb MLPProj + per-block k_img/v_img heads
    (reference tested-model set includes WAN i2v, /root/reference/README.md:5)."""

    def _fea(self, b=1):
        return jax.random.normal(
            jax.random.key(9), (b, 5, TINY_I2V.img_dim), jnp.float32
        )

    def test_bitwise_roundtrip_with_img_branch(self, tiny_wan_i2v):
        sd = _official_layout_sd(TINY_I2V, tiny_wan_i2v.params)
        assert "img_emb.proj.1.weight" in sd
        assert "blocks.0.cross_attn.k_img.weight" in sd
        got = convert_wan_checkpoint(sd, TINY_I2V)
        fg = dict(flatten_tree(got))
        fw = dict(flatten_tree(tiny_wan_i2v.params))
        assert sorted(fg) == sorted(fw)
        for k in fw:
            np.testing.assert_array_equal(fg[k], fw[k], err_msg=str(k))

    def test_clip_fea_changes_output(self, tiny_wan_i2v):
        x = jax.random.normal(jax.random.key(1), (1, 2, 4, 4, 9), jnp.float32)
        t = jnp.array([0.5])
        ctx = jax.random.normal(jax.random.key(2), (1, 6, 32), jnp.float32)
        m = tiny_wan_i2v
        base = np.asarray(jax.jit(m.apply)(m.params, x, t, ctx))
        with_img = np.asarray(
            jax.jit(m.apply)(m.params, x, t, ctx, clip_fea=self._fea())
        )
        assert base.shape == with_img.shape == (1, 2, 4, 4, 4)
        assert np.abs(base - with_img).max() > 1e-6

    def test_golden_converted_forward_matches(self, tiny_wan_i2v):
        sd = _official_layout_sd(TINY_I2V, tiny_wan_i2v.params)
        params = convert_wan_checkpoint(sd, TINY_I2V)
        x = jax.random.normal(jax.random.key(4), (1, 2, 4, 4, 9), jnp.float32)
        t = jnp.array([0.3])
        ctx = jax.random.normal(jax.random.key(5), (1, 6, 32), jnp.float32)
        f = jax.jit(tiny_wan_i2v.apply)
        want = f(tiny_wan_i2v.params, x, t, ctx, clip_fea=self._fea())
        got = f(params, x, t, ctx, clip_fea=self._fea())
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_clip_fea_on_t2v_config_raises(self, tiny_wan):
        x = jnp.zeros((1, 2, 4, 4, 4), jnp.float32)
        ctx = jnp.zeros((1, 6, 32), jnp.float32)
        with pytest.raises(ValueError, match="img_dim"):
            jax.jit(tiny_wan.apply)(
                tiny_wan.params, x, jnp.array([0.1]), ctx,
                clip_fea=jnp.zeros((1, 5, 24)),
            )

    def test_apply_i2v_conditioning_composes(self, tiny_wan_i2v):
        from comfyui_parallelanything_tpu.models.wan import (
            apply_i2v_conditioning,
        )

        cond = jax.random.normal(jax.random.key(6), (1, 2, 4, 4, 5))
        fea = self._fea()
        composed = apply_i2v_conditioning(tiny_wan_i2v, cond, fea)
        x = jax.random.normal(jax.random.key(7), (1, 2, 4, 4, 4), jnp.float32)
        t = jnp.array([0.5])
        ctx = jax.random.normal(jax.random.key(8), (1, 6, 32), jnp.float32)
        got = jax.jit(composed.apply)(composed.params, x, t, ctx)
        want = jax.jit(tiny_wan_i2v.apply)(
            tiny_wan_i2v.params,
            jnp.concatenate([x, cond.astype(x.dtype)], axis=-1),
            t, ctx, clip_fea=fea,
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # CFG's doubled batch tiles both conditioning tensors.
        x2 = jnp.concatenate([x, x], axis=0)
        got2 = jax.jit(composed.apply)(composed.params, x2, jnp.array([0.5, 0.5]),
                              jnp.concatenate([ctx, ctx], axis=0))
        np.testing.assert_allclose(
            np.asarray(got2[0]), np.asarray(got2[1]), atol=1e-5
        )


class TestI2VConditioningConfigAware:
    """apply_i2v_conditioning's host WAN21.concat_cond semantics (review
    fixes): zero-fill when no start-image cond, ignore on t2v checkpoints,
    reject mismatched widths at compose time."""

    def test_missing_cond_zero_fills(self, tiny_wan_i2v):
        from comfyui_parallelanything_tpu.models.wan import (
            apply_i2v_conditioning,
        )

        fea = jax.random.normal(jax.random.key(9), (1, 5, 24), jnp.float32)
        composed = apply_i2v_conditioning(tiny_wan_i2v, cond=None,
                                          clip_fea=fea)
        x = jax.random.normal(jax.random.key(1), (1, 2, 4, 4, 4), jnp.float32)
        t = jnp.array([0.5])
        ctx = jnp.zeros((1, 6, 32))
        got = jax.jit(composed.apply)(composed.params, x, t, ctx)
        want = jax.jit(tiny_wan_i2v.apply)(
            tiny_wan_i2v.params,
            jnp.concatenate([x, jnp.zeros((1, 2, 4, 4, 5))], axis=-1),
            t, ctx, clip_fea=fea,
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_t2v_checkpoint_ignores_tag(self, tiny_wan):
        from comfyui_parallelanything_tpu.models.wan import (
            apply_i2v_conditioning,
        )

        composed = apply_i2v_conditioning(
            tiny_wan, cond=jnp.zeros((1, 2, 4, 4, 5))
        )
        assert composed is tiny_wan  # stock: no concat slots → no-op

    def test_wrong_width_cond_rejected(self, tiny_wan_i2v):
        from comfyui_parallelanything_tpu.models.wan import (
            apply_i2v_conditioning,
        )

        with pytest.raises(ValueError, match="concatenates 5"):
            apply_i2v_conditioning(
                tiny_wan_i2v, cond=jnp.zeros((1, 2, 4, 4, 9))
            )
