"""Wan2.2-T2V-A14B against its plain reference at tiny widths on the CPU: two
experts of one family loaded at the depth their files have, resident in
bfloat16 behind one compiled step program; a LoRA baked into 16-bit resident
kernels tensor by tensor; the UMT5 tower on the ``wan`` wire; the routes and
counters at the cell's shapes. The video decoder is held to the reference in
``test_wan_reference_decoder.py``, the whole graph and the loader's residency
rule in ``test_wan_reference_graph.py``; ``wan_twin.py`` holds what the three
share."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from twins import _counted, _rel, twin_files  # noqa: F401 — a fixture; benchmark/ on the path
from wan_twin import _file, fresh_residency, tiny, tiny_bf16  # noqa: F401 — fixtures
from yardstick import reference_sd, reference_wan, safetensors_io

# -- the denoiser ---------------------------------------------------------------


@pytest.mark.parametrize("expert", [0, 1], ids=["high_noise", "low_noise"])
def test_tiny_wan_forward_of_each_expert_equals_the_reference_in_float32(tiny, expert):
    """models/wan.py at 2 blocks a file (the preset says 30: the depth is read
    off the file), full-width q/k RMS norms, a three-axis rotary of 8 / 4 / 4
    (16-wide heads), on a non-square clip, against ``reference_wan.wan``
    written from the published description. Both compute in float32 (conftest
    pins ``highest``); what is left is the order of the sums."""
    from comfyui_parallelanything_tpu import models
    from comfyui_parallelanything_tpu.models import load_wan_checkpoint

    cell, _, ref_kw = tiny
    m = cell["config_data"]["wan"]
    path = _file(cell, ref_kw, expert)
    model = load_wan_checkpoint(path, models.wan_1_3b_config())
    assert model.config.depth == m["num_layers"] == 2
    assert model.config.axes_dim == (8, 4, 4)
    keys = jax.random.split(jax.random.key(3 + expert), 2)
    x = jax.random.normal(keys[0], (1, 3, 4, 8, 16), jnp.float32)  # NTHWC
    context = jax.random.normal(keys[1], (1, 40, m["text_dim"]), jnp.float32)
    t = jnp.asarray([0.875], jnp.float32)
    got = jax.jit(model.apply)(model.params, x, t, context)
    w = reference_sd.load_weights(safetensors_io.read(path))
    # (one program each side, not a walk that compiles every operation alone)
    want = jax.jit(lambda x, t, c: reference_wan.wan("float32", w, m, x, t, c))(
        jnp.transpose(x, (0, 4, 1, 2, 3)), t, context)
    want = jnp.transpose(want, (0, 2, 3, 4, 1))
    assert got.shape == want.shape == x.shape
    assert _rel(got, want) < 1e-4, _rel(got, want)


def test_two_experts_of_one_family_share_one_step_program(tiny):
    """Two files, two parameter sets, two names for the spans and counters —
    and ONE module and ``apply`` function, so what jax traces and compiles for
    the first expert serves the second."""
    from comfyui_parallelanything_tpu import models
    from comfyui_parallelanything_tpu.models import load_wan_checkpoint

    cell, _, ref_kw = tiny
    high = load_wan_checkpoint(_file(cell, ref_kw, 0), models.wan_1_3b_config(), name="high")
    low = load_wan_checkpoint(_file(cell, ref_kw, 1), models.wan_1_3b_config(), name="low")
    assert high.apply is low.apply and high.config == low.config
    assert high.params is not low.params
    a, b = (jax.tree.leaves(m.params["blocks_0"]["ffn_in"])[0] for m in (high, low))
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_a_wan_file_is_resident_in_the_type_it_stores(tiny_bf16):
    """Under bfloat16 compute every matmul kernel of a bfloat16 file stays
    bfloat16 on the chip — the time path's and the head's too, widened where
    they are used — and norm scales, biases and modulation tables are float32:
    an expert is never whole in float32."""
    from comfyui_parallelanything_tpu import models
    from comfyui_parallelanything_tpu.models import load_wan_checkpoint

    cell, _, ref_kw = tiny_bf16
    model = load_wan_checkpoint(_file(cell, ref_kw, 0), models.wan_1_3b_config())
    p = model.params
    assert model.config.dtype == jnp.bfloat16
    for key in ("patch_embedding", "text_in", "time_in", "time_projection", "head_proj"):
        assert p[key]["kernel"].dtype == jnp.bfloat16, key
        assert p[key]["bias"].dtype == jnp.float32, key
    blk = p["blocks_1"]
    assert {blk[k]["kernel"].dtype for k in ("self_q", "cross_v", "ffn_in", "ffn_out")} \
        == {jnp.dtype(jnp.bfloat16)}
    assert blk["modulation"].dtype == blk["self_q_norm"]["scale"].dtype == jnp.float32
    by_type = {}
    for leaf in jax.tree.leaves(p):
        by_type[str(leaf.dtype)] = by_type.get(str(leaf.dtype), 0) + leaf.size
    assert by_type["bfloat16"] > 30 * by_type["float32"]


# -- LoRA on a 16-bit resident expert -------------------------------------------------


def test_a_lora_is_baked_into_bfloat16_resident_kernels_and_rounded_once(
        tiny_bf16):
    """``W + strength · (alpha / r) · up @ down`` on the ten linears of every
    block, the sum in float32 and the result rounded ONCE to the resident
    bfloat16 — the same sum made here by hand, to the last bit but for an
    element in a thousand; every
    other tensor is the unpatched expert's own, and nothing is unmatched."""
    from comfyui_parallelanything_tpu import models
    from comfyui_parallelanything_tpu.models import load_wan_checkpoint

    cell, _, ref_kw = tiny_bf16
    path, lora_path = _file(cell, ref_kw, 0), _file(cell, ref_kw, 2)
    base = load_wan_checkpoint(path, models.wan_1_3b_config())
    baked = load_wan_checkpoint(path, models.wan_1_3b_config(), lora=lora_path,
                                lora_strength=0.75)
    w, lora = safetensors_io.read(path), safetensors_io.read(lora_path)
    names = {"self_attn.q": "self_q", "cross_attn.o": "cross_o", "ffn.0": "ffn_in",
             "ffn.2": "ffn_out"}
    for torch_name, ours in names.items():
        key = f"blocks.1.{torch_name}"
        up = np.asarray(lora[f"diffusion_model.{key}.lora_up.weight"], np.float32)
        down = np.asarray(lora[f"diffusion_model.{key}.lora_down.weight"], np.float32)
        alpha = float(np.asarray(lora[f"diffusion_model.{key}.alpha"], np.float32))
        want = np.asarray(w[f"{key}.weight"], np.float32) \
            + 0.75 * (alpha / down.shape[0]) * (up @ down)
        want = jnp.asarray(want).astype(jnp.bfloat16).T
        got = baked.params["blocks_1"][ours]["kernel"]
        assert got.dtype == jnp.bfloat16
        # the same sum in another order of additions: equal, but for a rare
        # element within a float32 rounding of a bfloat16 boundary (one ulp)
        got32, want32 = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert (got32 != want32).mean() < 1e-3, key
        assert np.abs(got32 - want32).max() <= np.abs(want32).max() * 2.0 ** -7, key
        assert not np.array_equal(np.asarray(got, np.float32), np.asarray(
            base.params["blocks_1"][ours]["kernel"], np.float32))
    for ours in ("self_q_norm", "norm3"):
        assert np.array_equal(np.asarray(baked.params["blocks_1"][ours]["scale"]),
                              np.asarray(base.params["blocks_1"][ours]["scale"]))
    assert np.array_equal(np.asarray(baked.params["time_projection"]["kernel"], np.float32),
                          np.asarray(base.params["time_projection"]["kernel"], np.float32))


def test_bake_lora_touches_only_what_the_lora_names_and_only_when_taken():
    """``convert.bake_lora`` returns the base's own tensors (stored type and
    all) for every key the LoRA does not name, and computes a named one when
    it is taken: float32 on the default device, from whatever type the base
    stores."""
    import ml_dtypes

    from comfyui_parallelanything_tpu.models.convert import bake_lora

    rng = np.random.default_rng(0)
    sd = {"blocks.0.ffn.0.weight": rng.standard_normal((8, 4)).astype(ml_dtypes.bfloat16),
          "blocks.0.ffn.0.bias": rng.standard_normal(8).astype(np.float32),
          "head.head.weight": rng.standard_normal((4, 8)).astype(ml_dtypes.bfloat16)}
    lora = {"diffusion_model.blocks.0.ffn.0.lora_down.weight":
            rng.standard_normal((2, 4)).astype(np.float32),
            "diffusion_model.blocks.0.ffn.0.lora_up.weight":
            rng.standard_normal((8, 2)).astype(np.float32),
            "diffusion_model.blocks.0.ffn.0.alpha": np.asarray(1.0, np.float32)}
    out = bake_lora(sd, lora, 2.0)
    assert set(out) == set(sd) and len(out) == 3
    assert out["head.head.weight"] is sd["head.head.weight"]
    assert out["blocks.0.ffn.0.bias"] is sd["blocks.0.ffn.0.bias"]
    got = out["blocks.0.ffn.0.weight"]
    want = sd["blocks.0.ffn.0.weight"].astype(np.float32) + 2.0 * 0.5 * (
        lora["diffusion_model.blocks.0.ffn.0.lora_up.weight"]
        @ lora["diffusion_model.blocks.0.ffn.0.lora_down.weight"])
    assert got.dtype == jnp.float32 and np.allclose(np.asarray(got), want, rtol=1e-6)


# -- the tower on the wan wire ----------------------------------------------------------


def test_the_umt5_states_on_the_wan_wire_equal_the_reference(tiny, fresh_residency):
    """``CLIPLoader type=wan`` + ``CLIPTextEncode``: 512 rows, the tower's own
    mask inside (padded keys take no part), a position table in EVERY block,
    and zeros after the valid tokens on the way out — against
    ``reference_t5.encode`` with ``per_layer_bias`` and the same zeroing. The
    ``text-encode`` span names the tower and the VALID token count."""
    from comfyui_parallelanything_tpu import nodes_compat
    from comfyui_parallelanything_tpu.nodes import TPUTextEncode
    from comfyui_parallelanything_tpu.utils import tracing

    cell, ref_args, ref_kw = tiny
    config = cell["config_data"]
    name = os.path.basename(_file(cell, ref_kw, 4))
    (clip,) = nodes_compat.CLIPLoader().load(name, "wan")
    assert clip["type"] == "umt5" and clip["encoder"].cfg.per_layer_bias
    text = "harbor lantern meadow granite"
    was_on = tracing.on()
    tracing.enable()
    try:
        (cond,) = TPUTextEncode().encode(clip, text)
        spans = [e for e in tracing.export()["traceEvents"]
                 if e.get("name") == "text-encode" and e["args"].get("tower") == "umt5"]
    finally:
        if not was_on:
            tracing.disable()
    ref = reference_wan.Reference(config, *ref_args, "float32", **ref_kw)
    ids = np.stack([ref_kw["tokenizers"]["t5"].ids(text)])
    valid = int((ids != 0).sum())
    want = np.asarray(ref.text_states(ids, name))
    got = np.asarray(cond["context"])
    assert got.shape == want.shape == (1, 512, config["text_t5"]["d_model"])
    assert 1 < valid < 64
    assert not got[0, valid:].any() and got[0, :valid].any(axis=-1).all()
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 2e-5
    assert cond["pooled"] is None
    assert spans and spans[-1]["args"]["tokens"] == valid
    assert spans[-1]["args"]["cache"] == "miss"


# -- routes and counters at the cell's shapes ---------------------------------------------


def test_the_attention_routes_at_the_cells_shapes():
    """The two attention classes of the cell by ``tuning.route`` on a TPU: the
    self-attention (1, 20280, 40, 128) is ragged (20,280 is no multiple of
    128) and past ``RAGGED_ONE_BLOCK`` keys, so the fused kernel streams
    4096-key blocks and masks the last; the cross-attention's 512 keys are
    under ``PADDED_DIM_MIN_KEYS`` and stay with the XLA family."""
    import importlib

    from comfyui_parallelanything_tpu.ops.pallas import tuning

    threshold = importlib.import_module(
        "comfyui_parallelanything_tpu.ops.attention")._CHUNK_THRESHOLD
    tokens = 13 * 30 * 52
    assert tokens == 20280 and tokens % 128
    own = tuning.route(tokens, tokens, 128, 40, on_tpu=True, chunk_threshold=threshold)
    assert (own.backend, own.rule, own.block_k) == ("pallas", "ragged", 4096)
    assert tokens > tuning.RAGGED_ONE_BLOCK
    cross = tuning.route(tokens, 512, 128, 40, on_tpu=True, chunk_threshold=threshold)
    assert cross.backend.startswith("xla") and 512 < tuning.PADDED_DIM_MIN_KEYS


def test_the_route_and_prologue_counters_for_one_forward_at_the_cells_shapes(monkeypatch):
    """One block of the published widths traced (nothing computed:
    ``jax.eval_shape``) at the cell's 20,280 tokens routed as on the
    chip: the self-attention counts under
    ``pa_attention_key_blocks_total{rule=ragged, keys=streamed}``, and the
    full-width q/k norm with its rotary under
    ``pa_qk_prologue_total{path=xla}`` — the fused prologue serves neither."""
    from comfyui_parallelanything_tpu.models.wan import WanModel, build_wan, wan_14b_config
    import importlib

    att = importlib.import_module("comfyui_parallelanything_tpu.ops.attention")
    cfg = wan_14b_config(depth=1)
    model = build_wan(cfg, params={})
    shapes = jax.eval_shape(lambda: WanModel(cfg).init(
        jax.random.key(0), jnp.zeros((1, 13, 60, 104, 16)), jnp.zeros((1,)),
        jnp.zeros((1, 512, 4096)))["params"])
    keys = _counted("pa_attention_key_blocks_total", rule="ragged", keys="streamed")
    prologue = _counted("pa_qk_prologue_total", path="xla", rope="interleaved")
    routed = _counted("pa_attention_route_total", backend="pallas")
    xla = _counted("pa_attention_route_total", backend="xla_chunked") \
        + _counted("pa_attention_route_total", backend="xla")
    # as on the chip: ``route`` under ``auto`` with the fused kernel in reach
    monkeypatch.setattr(att, "_pallas_available", lambda: True)
    out = jax.eval_shape(
        model.apply, shapes, jax.ShapeDtypeStruct((1, 13, 60, 104, 16), jnp.float32),
        jax.ShapeDtypeStruct((1,), jnp.float32),
        jax.ShapeDtypeStruct((1, 512, 4096), jnp.float32))
    assert out.shape == (1, 13, 60, 104, 16)
    assert _counted("pa_qk_prologue_total", path="xla", rope="interleaved") == prologue + 1
    assert _counted("pa_attention_key_blocks_total", rule="ragged",
                    keys="streamed") == keys + 1
    assert _counted("pa_attention_route_total", backend="pallas") == routed + 1
    assert _counted("pa_attention_route_total", backend="xla_chunked") \
        + _counted("pa_attention_route_total", backend="xla") == xla + 1
