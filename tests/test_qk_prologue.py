"""The q/k prologue (ops/attention.qk_prologue): the one-pass Pallas kernel
against the jnp functions it stands in for, the rule that chooses between
them, and the counter that says which a traced program holds. CPU: the
kernel runs in the Pallas interpreter; what only the TPU's compiler can show
is in tests/test_tpu_compile.py."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_parallelanything_tpu.ops.basic import rms_normalize
from comfyui_parallelanything_tpu.ops.pallas.qk_prologue import (
    heads_a_step,
    qk_prologue_call,
    rope_tables,
)
from comfyui_parallelanything_tpu.ops.pallas.tuning import (
    QK_PROLOGUE_MIN_ELEMENTS,
    qk_prologue_route,
)
from comfyui_parallelanything_tpu.ops.rope import apply_rope, axis_rope_freqs
from comfyui_parallelanything_tpu.utils.metrics import registry

att = importlib.import_module("comfyui_parallelanything_tpu.ops.attention")


def _case(batch, rows, heads, dim, rotary, blocks, dtype, seed=0):
    """Sources, scales and rotary tables of one case, and what the jnp
    functions make of them. ``blocks`` column blocks of H·D in the source
    array (q and k its first two), or 0: q and k are arrays of their own."""
    keys = jax.random.split(jax.random.key(seed), 5)
    width = heads * dim

    def make(key, n):
        return (3.0 * jax.random.normal(key, (batch, rows, n * width))).astype(dtype)

    if blocks:
        src = make(keys[0], blocks)
        view = src.reshape(batch, rows, blocks, heads, dim)
        q, k, srcs = view[:, :, 0], view[:, :, 1], (src, None)
    else:
        srcs = (make(keys[0], 1), make(keys[1], 1))
        q, k = (x.reshape(batch, rows, heads, dim) for x in srcs)
    scales = tuple(1.0 + 0.1 * jax.random.normal(key, (dim,)) for key in keys[2:4])
    rope = None
    if rotary:
        ids = jax.random.randint(keys[4], (batch, rows, 3), 0, 64)
        rope = axis_rope_freqs(ids, (dim // 4, 3 * dim // 8, 3 * dim // 8), 256.0)
    def functions(q, k):
        q, k = rms_normalize(q, scales[0]), rms_normalize(k, scales[1])
        if rope is not None:
            q, k = apply_rope(q, *rope), apply_rope(k, *rope)
        return q, k

    # The jnp functions one after the other (the norm rounded to the
    # operands' type, then the rotary's result), and the same in float32
    # throughout, rounded once.
    once = tuple(x.astype(dtype) for x in functions(
        q.astype(jnp.float32), k.astype(jnp.float32)))
    return srcs, scales, rope, functions(q, k), once


# (label, batch, rows, heads, head dim, rotary, source blocks, dtype,
#  rows a grid step, lanes a grid step)
KERNEL_CASES = [
    # SD3.5's class: 64-wide heads, norm only, q and k read out of a fused qkv
    ("d64-norm-fused-bf16", 2, 96, 4, 64, False, 3, jnp.bfloat16, 32, 4096),
    ("d64-norm-fused-f32", 2, 96, 4, 64, False, 3, jnp.float32, 32, 4096),
    # two heads a column tile, two tiles: the 128-lane groups of 64-wide heads
    ("d64-norm-tiled", 1, 64, 4, 64, False, 3, jnp.bfloat16, 64, 128),
    # FLUX's classes: 128-wide heads, norm + rotary, out of a qkv projection
    # and out of linear1's seven column blocks
    ("d128-rope-fused-bf16", 1, 64, 2, 128, True, 3, jnp.bfloat16, 32, 4096),
    ("d128-rope-linear1-bf16", 1, 64, 2, 128, True, 7, jnp.bfloat16, 64, 128),
    ("d128-rope-fused-f32", 1, 64, 2, 128, True, 3, jnp.float32, 32, 4096),
    # Z-Image's: q and k arrays of their own, three heads in two-then-one
    # tiles, and a row count no block divides (4173-like: 173 = 2 x 64 + 45)
    ("d128-rope-separate-ragged", 1, 173, 3, 128, True, 0, jnp.bfloat16, 64, 128),
    ("d64-norm-separate-ragged", 2, 77, 2, 64, False, 0, jnp.bfloat16, 64, 4096),
    ("d128-norm-only", 1, 48, 2, 128, False, 3, jnp.bfloat16, 16, 4096),
    ("d128-rope-ragged-f32", 2, 45, 2, 128, True, 0, jnp.float32, 32, 4096),
]


@pytest.mark.parametrize(
    "label,batch,rows,heads,dim,rotary,blocks,dtype,block_rows,tile_lanes",
    KERNEL_CASES, ids=[c[0] for c in KERNEL_CASES])
def test_kernel_matches_the_jnp_functions(label, batch, rows, heads, dim, rotary,
                                          blocks, dtype, block_rows, tile_lanes):
    """Statistics (sums off the MXU, exact products, float32 adds), products
    and the rotary in float32, rounded ONCE to the
    operands' type: the kernel's result is the jnp functions' computed in
    float32 throughout, to one unit in the last place (the rotary's two
    products may be summed in the other order) — which is what XLA makes of
    them inside one program on the chip — and within the norm's own rounding,
    one unit of the operands' size, of the functions called one after the
    other."""
    (q_src, k_src), scales, rope, twice, once = _case(
        batch, rows, heads, dim, rotary, blocks, dtype)
    got = qk_prologue_call(
        q_src, k_src, *scales, None if rope is None else rope_tables(*rope),
        heads=heads, eps=1e-6, block_rows=block_rows, tile_lanes=tile_lanes,
        interpret=True)
    # One unit of bfloat16; of float32 a few (the sum's order), and of the
    # operands' size where the rotary's two products cancel.
    ulp = float(jnp.finfo(dtype).eps) * (1 if dtype == jnp.bfloat16 else 4)
    for w2, w1, g in zip(twice, once, got):
        assert g.shape == (batch, rows, heads * dim) and g.dtype == dtype
        w2, w1, g = (np.asarray(x.reshape(g.shape).astype(jnp.float32))
                     for x in (w2, w1, g))
        assert np.all(np.isfinite(g))
        np.testing.assert_allclose(g, w1, rtol=ulp, atol=1e-5)
        np.testing.assert_allclose(g, w2, rtol=ulp, atol=ulp * np.abs(w2).max())
        if not rotary and dtype == jnp.bfloat16:
            # The norm alone of bfloat16 operands is the functions' bit for
            # bit (the sums differ in float32's last bit at most).
            assert np.array_equal(g, w2)


def test_heads_a_step_fills_whole_lane_groups():
    assert heads_a_step(24, 64, 4096) == 24      # SD3.5: the full 1536 lanes
    assert heads_a_step(24, 64, 128) == 2        # two 64-wide heads a group
    assert heads_a_step(24, 64, 64) == 2         # never half a lane group
    assert heads_a_step(30, 128, 1024) == 6      # Z-Image: 768 of 3840 lanes
    assert heads_a_step(24, 128, 1024) == 8
    assert heads_a_step(3, 128, 256) == 1        # 2 does not divide 3


# The entry point's choice, read from what the call shows.
# (label, on a TPU, pin, batch, rows, heads, head dim, rotary, fused)
ROUTES = [
    ("sd35m-x4096", True, "auto", 2, 4096, 24, 64, False, True),
    ("sd35m-ctx77", True, "auto", 2, 77, 24, 64, False, False),
    ("sd35m-512sq-x1024", True, "auto", 2, 1024, 24, 64, False, True),
    ("flux-512sq-img1024", True, "auto", 1, 1024, 24, 128, True, True),
    ("flux-single4352", True, "auto", 1, 4352, 24, 128, True, True),
    ("flux-img4096", True, "auto", 1, 4096, 24, 128, True, True),
    ("flux-txt256", True, "auto", 1, 256, 24, 128, True, True),
    ("zimage-joint4128", True, "auto", 1, 4128, 30, 128, True, True),
    ("zimage-refine4096", True, "auto", 1, 4096, 30, 128, True, True),
    ("zimage-cap32", True, "auto", 1, 32, 30, 128, True, False),
    # a text tower's rows: Qwen3's 256 tokens of 32 heads, T5's of 64
    ("tower-16", True, "auto", 1, 16, 32, 128, False, False),
    # off a TPU, and under a pin of the XLA family, never
    ("flux-single4352-cpu", False, "auto", 1, 4352, 24, 128, True, False),
    ("sd35m-x4096-pinned-xla", True, "xla", 2, 4096, 24, 64, False, False),
    ("sd35m-x4096-pinned-chunked", True, "xla_chunked", 2, 4096, 24, 64, False, False),
    # a pinned pallas takes every shape the kernel is written for
    ("sd35m-ctx77-pinned", False, "pallas", 2, 77, 24, 64, False, True),
    # ... and no other: the rotary on 64-wide heads, 40- and 256-wide heads,
    # a width that is not whole lane groups
    ("rope-d64", True, "pallas", 1, 4096, 24, 64, True, False),
    ("d40", True, "auto", 16, 4096, 8, 40, False, False),
    ("d256", True, "pallas", 1, 4096, 12, 256, False, False),
    ("odd-width", True, "auto", 1, 4096, 3, 64, False, False),
]


@pytest.mark.parametrize(
    "label,tpu,pin,batch,rows,heads,dim,rotary,fused", ROUTES,
    ids=[r[0] for r in ROUTES])
def test_route_is_read_from_the_call(monkeypatch, label, tpu, pin, batch, rows,
                                     heads, dim, rotary, fused):
    """``qk_prologue_route`` names the path from the call's shape, the
    backend and the pin; ``ops/attention.qk_prologue`` executes that answer
    and counts it once a trace."""
    qp = importlib.import_module(
        "comfyui_parallelanything_tpu.ops.pallas.qk_prologue")
    assert qk_prologue_route(batch * rows, heads, dim, rotary, on_tpu=tpu,
                             pinned=pin) is fused
    monkeypatch.setattr(att, "_pallas_available", lambda: tpu)
    monkeypatch.setattr(att, "_BACKEND", pin)
    calls = []

    def stub(q_src, k_src, q_scale, k_scale, tables, **kw):
        calls.append((q_src.shape, k_src is None, tables is not None, kw["heads"]))
        out = jax.ShapeDtypeStruct((*q_src.shape[:2], kw["heads"] * dim), q_src.dtype)
        return jnp.zeros(out.shape, out.dtype), jnp.zeros(out.shape, out.dtype)

    monkeypatch.setattr(qp, "qk_prologue_call", stub)
    labels = {"path": "fused" if fused else "xla",
              "rope": "interleaved" if rotary else "none"}

    def count():
        return registry.get("pa_qk_prologue_total", labels) or 0.0

    before = count()
    qkv = jax.ShapeDtypeStruct((batch, rows, 3, heads, dim), jnp.bfloat16)
    scale = jax.ShapeDtypeStruct((dim,), jnp.float32)
    rope = (jax.ShapeDtypeStruct((batch, rows, dim // 2), jnp.float32),) * 2
    # A fresh function each case: a cached trace counts nothing.
    fn = jax.jit(lambda x, a, b, r: att.qk_prologue(
        x, a, b, rope=r if rotary else None))
    q, k = fn.eval_shape(qkv, scale, scale, rope)
    assert q.shape == k.shape == (batch, rows, heads, dim)
    assert q.dtype == jnp.bfloat16
    assert count() == before + 1
    fn.eval_shape(qkv, scale, scale, rope)
    assert count() == before + 1  # once a trace, not once a call
    # The fused source goes to the kernel whole: nothing is sliced first.
    want = [((batch, rows, 3 * heads * dim), True, rotary, heads)] if fused else []
    assert calls == want


def test_threshold_stands_at_flux_256_text_tokens():
    """The smallest class measured whose win is above 0.02 ms a call
    (scripts/bench_kernels.py --prologue; the table beside the constant)."""
    assert QK_PROLOGUE_MIN_ELEMENTS == 256 * 24 * 128


def _denoiser_counts(monkeypatch, trace):
    """The counter's moves over one abstract trace of a denoiser with the
    backend reading as a TPU (nothing is compiled: ``eval_shape``)."""
    fa = importlib.import_module(
        "comfyui_parallelanything_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(att, "_pallas_available", lambda: True)
    monkeypatch.setattr(fa, "flash_attention", lambda q, k, v, **kw: q)

    def counts():
        return {(path, rope): registry.get(
                    "pa_qk_prologue_total", {"path": path, "rope": rope}) or 0.0
                for path in ("fused", "xla") for rope in ("none", "interleaved")}

    before = counts()
    trace()
    return {key: n - before[key] for key, n in counts().items() if n != before[key]}


def test_sd35m_step_holds_37_fused_and_24_xla_prologues(monkeypatch):
    """The cell sd35m-b1-1024.closed's denoiser at published widths (CFG
    doubles the batch): the image stream of 24 joint and 13 dual attentions
    on the kernel, the 77 text tokens of 24 context streams on XLA."""
    from comfyui_parallelanything_tpu.models.mmdit import (
        MMDiTModel, sd35_medium_config,
    )

    cfg = sd35_medium_config()
    module = MMDiTModel(cfg)
    args = (jax.ShapeDtypeStruct((2, 128, 128, 16), jnp.float32),
            jax.ShapeDtypeStruct((2,), jnp.float32),
            jax.ShapeDtypeStruct((2, 77, cfg.context_in_dim), jnp.float32),
            jax.ShapeDtypeStruct((2, cfg.pooled_dim), jnp.float32))

    def trace():
        # ``init`` walks the forward pass once to shape the parameters: that
        # one abstract walk is the step's, and no second one is paid for
        return jax.eval_shape(
            lambda x, t, c, y: module.init(jax.random.key(0), x, t, c, y=y), *args)

    moved = _denoiser_counts(monkeypatch, trace)
    assert moved == {("fused", "none"): 37, ("xla", "none"): 24}


def test_flux_schnell_step_holds_twelve_fused_prologues(monkeypatch):
    """flux-schnell-b1-1024.closed-unique at the cut's 3 + 6 blocks: a double
    block's image (4096 rows) and text (256 rows, the threshold's class)
    streams and every single block's 4352 rows on the kernel."""
    from comfyui_parallelanything_tpu.models.flux import (
        FluxModel, flux_abstract_params, flux_schnell_config,
    )

    cfg = flux_schnell_config(depth=3, depth_single_blocks=6)
    shape, txt_len = (1, 128, 128, 16), 256
    params = flux_abstract_params(cfg, shape, txt_len)

    def trace():
        return jax.eval_shape(
            lambda p, x, t, c, y: FluxModel(cfg).apply(
                {"params": p}, x, t, c, y=y),
            params, jax.ShapeDtypeStruct(shape, jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.float32),
            jax.ShapeDtypeStruct((1, txt_len, cfg.context_in_dim), jnp.float32),
            jax.ShapeDtypeStruct((1, cfg.vec_in_dim), jnp.float32))

    moved = _denoiser_counts(monkeypatch, trace)
    assert moved == {("fused", "interleaved"): 3 + 3 + 6}


def test_zimage_step_holds_ten_fused_and_two_xla_prologues(monkeypatch):
    """zimage-turbo-b1-1024.closed-unique at the cut's 8 main layers: those
    and the two noise refiners on the kernel, the context refiner's 32
    caption tokens on XLA."""
    from comfyui_parallelanything_tpu.models.zimage import (
        ZImageModel, zimage_turbo_config,
    )

    cfg = zimage_turbo_config(n_layers=8)
    module = ZImageModel(cfg)
    args = (jax.ShapeDtypeStruct((1, 128, 128, 16), jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.float32),
            jax.ShapeDtypeStruct((1, 32, cfg.cap_feat_dim), jnp.float32))

    def trace():  # the parameters' shapes and the counts from one walk
        return jax.eval_shape(
            lambda *a: module.init(jax.random.key(0), *a), *args)

    moved = _denoiser_counts(monkeypatch, trace)
    assert moved == {("fused", "interleaved"): 10, ("xla", "interleaved"): 2}


def test_entry_point_runs_the_kernel_under_a_pin_and_matches(monkeypatch):
    """End to end through the entry point on the CPU: a pinned ``pallas``
    runs the kernel in the interpreter, on a fused source and on a pair."""
    monkeypatch.setattr(att, "_BACKEND", "pallas")
    for blocks in (3, 0):
        (q_src, k_src), scales, rope, want, _ = _case(
            1, 40, 2, 128, True, blocks, jnp.bfloat16, seed=blocks)
        qkv = (q_src.reshape(1, 40, blocks, 2, 128) if blocks else
               (q_src.reshape(1, 40, 2, 128), k_src.reshape(1, 40, 2, 128)))
        got = att.qk_prologue(qkv, *scales, rope=rope)
        for w, g in zip(want, got):
            assert g.shape == w.shape and g.dtype == w.dtype
            np.testing.assert_allclose(
                np.asarray(g.astype(jnp.float32)),
                np.asarray(w.astype(jnp.float32)), rtol=2 ** -7, atol=2 ** -5)


def test_batch_sharded_operands_under_a_data_mesh(monkeypatch):
    """Under ``jax.set_mesh`` with a ``data`` axis the kernel runs inside a
    shard_map over the axis (a Mosaic call cannot be partitioned), each device
    on its own rows; a batch the axis does not divide, or a mesh partitioned
    over another axis, takes the jnp path."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    monkeypatch.setattr(att, "_BACKEND", "pallas")
    (q_src, _), scales, rope, want, _ = _case(2, 48, 2, 128, True, 3, jnp.bfloat16)
    qkv = q_src.reshape(2, 48, 3, 2, 128)
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    rows = NamedSharding(mesh, P("data"))
    fn = jax.jit(lambda x, r: att.qk_prologue(x, *scales, rope=r))

    def fused_count():
        return registry.get("pa_qk_prologue_total",
                            {"path": "fused", "rope": "interleaved"}) or 0.0

    before = fused_count()
    with jax.set_mesh(mesh):
        got = fn(jax.device_put(qkv, rows),
                 tuple(jax.device_put(t, rows) for t in rope))
    assert fused_count() == before + 1
    for w, g in zip(want, got):
        assert g.sharding.spec == P("data")
        np.testing.assert_allclose(
            np.asarray(g.astype(jnp.float32)),
            np.asarray(w.astype(jnp.float32)), rtol=2 ** -7, atol=2 ** -5)

    # Three rows over two devices, or a ``model`` axis: no Mosaic call.
    with jax.set_mesh(mesh):
        assert att._mosaic_reach(2) and not att._mosaic_reach(3)
    with jax.set_mesh(Mesh(np.array(jax.devices()[:2]), ("model",))):
        assert not att._mosaic_reach(2)
    assert att._mosaic_reach(3)  # no context mesh: any batch


def test_sequence_parallel_context_takes_the_jnp_path(monkeypatch):
    """Inside ``sequence_parallel`` attention() hands its call to the
    sequence-parallel program, whose operands are sharded over ``seq``: the
    prologue before it stays in jnp, whatever the pin."""
    from jax.sharding import Mesh

    monkeypatch.setattr(att, "_BACKEND", "pallas")
    (q_src, _), scales, rope, want, _ = _case(1, 32, 2, 128, True, 3, jnp.bfloat16)
    mesh = Mesh(np.array(jax.devices()[:2]), ("seq",))

    def xla_count():
        return registry.get("pa_qk_prologue_total",
                            {"path": "xla", "rope": "interleaved"}) or 0.0

    before = xla_count()
    with att.sequence_parallel(mesh):
        got = att.qk_prologue(q_src.reshape(1, 32, 3, 2, 128), *scales, rope=rope)
    assert xla_count() == before + 1
    for w, g in zip(want, got):
        assert np.array_equal(np.asarray(g.astype(jnp.float32)),
                              np.asarray(w.astype(jnp.float32)))
