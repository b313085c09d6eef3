"""Tests for shared ops: timestep embedding, attention backends, pallas flash kernel
(interpreter mode on the CPU platform)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_parallelanything_tpu.ops import attention, timestep_embedding
from comfyui_parallelanything_tpu.ops.attention import _xla_attention
from comfyui_parallelanything_tpu.ops.pallas.flash_attention import flash_attention


class TestTimestepEmbedding:
    def test_shape_and_range(self):
        emb = timestep_embedding(jnp.arange(4, dtype=jnp.float32), 128)
        assert emb.shape == (4, 128)
        assert np.all(np.abs(np.asarray(emb)) <= 1.0 + 1e-6)

    def test_odd_dim(self):
        emb = timestep_embedding(jnp.ones((2,)), 65)
        assert emb.shape == (2, 65)

    def test_t_zero_finite(self):
        emb = timestep_embedding(jnp.zeros((1,)), 64)
        assert np.all(np.isfinite(np.asarray(emb)))


def _qkv(b=2, sq=64, sk=48, h=4, d=32, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, sq, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, sk, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, sk, h, d)), jnp.float32)
    return q, k, v


class TestAttention:
    def test_xla_softmax_rows_sum(self):
        q, k, v = _qkv()
        out = attention(q, k, v)
        assert out.shape == q.shape

    def test_self_vs_manual(self):
        q, k, v = _qkv(b=1, sq=8, sk=8, h=1, d=4)
        out = np.asarray(attention(q, k, v))[0, :, 0, :]
        qm, km, vm = (np.asarray(a)[0, :, 0, :] for a in (q, k, v))
        logits = qm @ km.T / np.sqrt(4)
        probs = np.exp(logits - logits.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        np.testing.assert_allclose(out, probs @ vm, rtol=1e-5, atol=1e-6)


class TestBackendEnvOverride:
    """PA_TPU_ATTENTION_BACKEND seeds the startup backend (ops/attention.py
    _initial_backend) so a driving process can force the safe XLA path for
    every child it spawns."""

    def test_env_forces_xla(self, monkeypatch):
        import importlib

        mod = importlib.import_module("comfyui_parallelanything_tpu.ops.attention")

        monkeypatch.setenv("PA_TPU_ATTENTION_BACKEND", "xla")
        assert mod._initial_backend() == "xla"

    def test_invalid_env_falls_back_to_auto(self, monkeypatch):
        import importlib

        mod = importlib.import_module("comfyui_parallelanything_tpu.ops.attention")

        monkeypatch.setenv("PA_TPU_ATTENTION_BACKEND", "cuda")
        assert mod._initial_backend() == "auto"

    def test_unset_env_is_auto(self, monkeypatch):
        import importlib

        mod = importlib.import_module("comfyui_parallelanything_tpu.ops.attention")

        monkeypatch.delenv("PA_TPU_ATTENTION_BACKEND", raising=False)
        assert mod._initial_backend() == "auto"

    def test_resolved_backends_records_actual_path(self):
        # Evidence labeling: after a call, resolved_backends() names the path
        # that actually served it ("auto" never appears) — bench.py stamps
        # this into every measured record.
        import importlib

        mod = importlib.import_module("comfyui_parallelanything_tpu.ops.attention")

        q, k, v = _qkv(b=1, sq=8, sk=8, h=1, d=4)
        mod.attention_local(q, k, v)  # CPU + unaligned shapes -> xla
        assert "xla" in mod.resolved_backends()
        assert "auto" not in mod.resolved_backends()


class TestChunkedAttention:
    """Memory-bounded XLA attention (lax.scan over query blocks): the only
    path that fits SD-class 1024² attention (40/64-dim heads, pallas-
    ineligible) on one chip — S×S logits never materialize."""

    def _mod(self):
        import importlib

        return importlib.import_module("comfyui_parallelanything_tpu.ops.attention")

    def test_matches_plain_xla(self, monkeypatch):
        att = self._mod()
        q, k, v = _qkv(b=2, sq=96, sk=64, h=2, d=16, seed=3)
        # Force several scan blocks: threshold smaller than the logits size.
        monkeypatch.setattr(att, "_CHUNK_THRESHOLD", 2 * 2 * 64 * 16)
        out = att._xla_chunked_attention(q, k, v, scale=16 ** -0.5)
        ref = att._xla_attention(q, k, v, scale=16 ** -0.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-2, atol=2e-2)  # bf16-scale matmuls

    def test_non_divisible_sq_padding(self, monkeypatch):
        att = self._mod()
        q, k, v = _qkv(b=1, sq=53, sk=40, h=2, d=8, seed=4)  # 53 % block != 0
        monkeypatch.setattr(att, "_CHUNK_THRESHOLD", 1 * 2 * 40 * 16)
        out = att._xla_chunked_attention(q, k, v, scale=8 ** -0.5)
        ref = att._xla_attention(q, k, v, scale=8 ** -0.5)
        assert out.shape == q.shape
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-2, atol=2e-2)

    def test_small_shapes_fall_through_to_plain(self):
        att = self._mod()
        q, k, v = _qkv(b=1, sq=8, sk=8, h=1, d=4)
        # Default threshold is far above this shape: identical single-pass path.
        out = att._xla_chunked_attention(q, k, v, scale=0.5)
        ref = att._xla_attention(q, k, v, scale=0.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)

    def test_auto_routes_big_logits_to_chunked(self, monkeypatch):
        att = self._mod()
        monkeypatch.setattr(att, "_CHUNK_THRESHOLD", 64)
        monkeypatch.setattr(att, "_RESOLVED", set())
        q, k, v = _qkv(b=1, sq=32, sk=32, h=2, d=8)
        att.attention_local(q, k, v)  # 1*2*32*32 = 2048 > 64 -> chunked
        assert att.resolved_backends() == ("xla_chunked",)

    def test_explicit_backend_name(self, monkeypatch):
        att = self._mod()
        att.set_attention_backend("xla_chunked")
        try:
            monkeypatch.setattr(att, "_RESOLVED", set())
            q, k, v = _qkv(b=1, sq=16, sk=16, h=1, d=4)
            out = att.attention_local(q, k, v)
            assert out.shape == q.shape
            assert att.resolved_backends() == ("xla_chunked",)
        finally:
            att.set_attention_backend("auto")


# What ``auto`` resolves to on a TPU at the XLA family's default threshold.
def _route(sq, sk, d, bh, **kw):
    from comfyui_parallelanything_tpu.ops.pallas.tuning import route

    kw = {"on_tpu": True, "chunk_threshold": 2**27, **kw}
    return route(sq, sk, d, bh, **kw)


class TestRoute:
    """``ops/pallas/tuning.route``: the one place that names an attention
    call's backend and blocks. Pure Python: nothing here compiles."""

    def _att(self):
        import importlib

        return importlib.import_module("comfyui_parallelanything_tpu.ops.attention")

    # Both sides of every threshold of the rule.
    # (label, route arguments, on a TPU, backend, blocks, rule)
    THRESHOLDS = [
        # PADDED_DIM_MIN_KEYS, at a 40-wide head and B·H 128
        ("keys-896", (4096, 896, 40, 128), True, "xla_chunked", None, "padded-dim"),
        ("keys-1024", (4096, 1024, 40, 128), True, "pallas", (256, 4096), "padded-dim"),
        # PADDED_DIM_MIN_LOGITS: 2^27 is B·H 128 at 1024 x 1024
        ("bh-127", (1024, 1024, 80, 127), True, "xla", None, "padded-dim"),
        ("bh-128", (1024, 1024, 80, 128), True, "pallas", (256, 4096), "padded-dim"),
        # RAGGED_MIN_LOGITS: B·H 48 at 1101 x 1101
        ("ragged-bh-47", (1101, 1101, 64, 47), True, "xla", None, "ragged"),
        ("ragged-bh-48", (1101, 1101, 64, 48), True, "pallas", (384, 1152), "ragged"),
        # RAGGED_ONE_BLOCK: a row padded to 4352 keys is one key block, a
        # longer one streams 4096 keys a block
        ("ragged-pads-to-4352", (4300, 4300, 64, 48), True, "pallas", (256, 4352), "ragged"),
        ("ragged-pads-to-4480", (4400, 4400, 64, 48), True, "pallas", (256, 4096), "ragged"),
        # 384 queries a block where they divide the padded row, else 256
        ("ragged-2304-by-384", (2250, 2250, 64, 48), True, "pallas", (384, 2304), "ragged"),
        ("ragged-2432-by-256", (2400, 2400, 64, 48), True, "pallas", (256, 2432), "ragged"),
        # the XLA family off a TPU: whole up to 2^27 logits, chunked above
        # (2^27 + 1 = 513 x 261633)
        ("cpu-2^27", (1024, 1024, 80, 128), False, "xla", None, "off-tpu"),
        ("cpu-2^27+1", (513, 261633, 80, 1), False, "xla_chunked", None, "off-tpu"),
    ]

    @pytest.mark.parametrize("label,args,tpu,backend,blocks,rule", THRESHOLDS,
                             ids=[c[0] for c in THRESHOLDS])
    def test_both_sides_of_every_threshold(self, label, args, tpu, backend,
                                           blocks, rule):
        got = _route(*args, on_tpu=tpu)
        assert got == (backend, *(blocks or (None, None)), rule)

    # LANE_ALIGNED_ONE_BLOCK_BYTES, both sides: 8 MB of one head's K are
    # 32,768 keys x 128 lanes in bfloat16, 16,384 in float32, 8192 x 512 lanes.
    # (label, keys, head dim, item size, keys a block)
    ONE_BLOCK = [
        ("bf16-32768", 32768, 128, 2, 32768), ("bf16-32896", 32896, 128, 2, 4096),
        ("f32-16384", 16384, 128, 4, 16384), ("f32-16512", 16512, 128, 4, 4096),
        ("512wide-8192", 8192, 512, 2, 8192), ("512wide-8320", 8320, 512, 2, 4096),
    ]

    @pytest.mark.parametrize("label,sk,d,itemsize,block_k", ONE_BLOCK,
                             ids=[c[0] for c in ONE_BLOCK])
    def test_a_lane_aligned_row_is_one_key_block_up_to_a_budget_in_bytes(
            self, label, sk, d, itemsize, block_k):
        got = _route(4096, sk, d, 12, itemsize=itemsize)
        assert got == ("pallas", 256, block_k, "lane-aligned")
        # what attention_local and the planner ask (the planner at bfloat16)
        att = self._att()
        assert att.resolve_route(4096, sk, d, 12, itemsize)[1:3] == (
            (256, block_k) if att._pallas_available() else (None, None))

    # A pin is served as pinned, on a TPU or off one, with the rule's blocks
    # where the rule has them at that shape.
    PINS = [
        ("pallas-where-the-rule-says-xla", "pallas", (256, 256, 160, 128), "pallas", (256, 256)),
        ("pallas-at-a-padded-dim-class", "pallas", (4096, 4096, 40, 128), "pallas", (256, 4096)),
        ("pallas-at-a-ragged-class", "pallas", (4173, 4173, 64, 48), "pallas", (384, 4224)),
        ("pallas-at-a-lane-aligned-class", "pallas", (4352, 4352, 128, 24), "pallas", (256, 4352)),
        ("xla-over-the-threshold", "xla", (4096, 4096, 40, 128), "xla_chunked", None),
        ("xla_chunked-at-a-small-shape", "xla_chunked", (16, 16, 4, 1), "xla_chunked", None),
        ("unknown", "pallas_mosaic", (16, 16, 4, 1), None, None),
    ]

    @pytest.mark.parametrize("label,pin,args,backend,blocks", PINS,
                             ids=[c[0] for c in PINS])
    @pytest.mark.parametrize("tpu", [True, False], ids=["tpu", "cpu"])
    def test_a_pin_is_served_as_pinned(self, tpu, label, pin, args, backend,
                                       blocks):
        if backend is None:
            with pytest.raises(ValueError, match="unknown attention backend"):
                _route(*args, on_tpu=tpu, pinned=pin)
            return
        got = _route(*args, on_tpu=tpu, pinned=pin)
        assert got == (backend, *(blocks or (None, None)), "pinned")

    @pytest.mark.parametrize("shrinks,threshold", [(1, 2**26), (7, 2**20),
                                                    (9, 2**20)],
                             ids=["one", "all", "past-the-floor"])
    def test_ladder_shrinks_move_the_xla_boundary_only(self, monkeypatch,
                                                       shrinks, threshold):
        """The ladder's attn-chunk-shrink rung halves where the XLA family
        starts chunking and stops at the floor; no fused route moves."""
        from test_planner import TestAttentionAxis

        att = self._att()
        monkeypatch.setattr(att, "_pallas_available", lambda: True)
        fused = [(sq, sk, d, b * h)
                 for _, tpu, b, sq, sk, h, d, backend, _ in TestAttentionAxis.ROUTES
                 if tpu and backend == "pallas"]
        before = [att.resolve_route(*c) for c in fused]
        try:
            said = [att.shrink_chunk_threshold() for _ in range(shrinks)]
            assert said[-1] == (threshold if shrinks <= 7 else None)
            assert att.chunk_config() == {"chunk_elems": threshold,
                                          "degraded": True}
            # 1024 x 1024 at a 64-wide head stays in the XLA family on a TPU
            # (under 2^27 logits): B·H rows of 2^20 logits each.
            rows = threshold // 2**20
            assert att.resolve_route(1024, 1024, 64, rows).backend == "xla"
            assert att.resolve_route(
                1024, 1024, 64, rows + 1).backend == "xla_chunked"
            assert [att.resolve_route(*c) for c in fused] == before
        finally:
            att.reset_chunk_shrink()
        assert att.chunk_config() == {"chunk_elems": 2**27, "degraded": False}

    @pytest.mark.parametrize("label", ["sd15-self4096", "sdxl-self1024",
                                       "sd35m-joint4173",
                                       "flux-schnell-joint4352"])
    def test_the_planner_records_the_same_route(self, monkeypatch, label):
        from test_planner import TestAttentionAxis

        from comfyui_parallelanything_tpu.parallel import planner

        _, _, b, sq, sk, h, d, backend, blocks = next(
            r for r in TestAttentionAxis.ROUTES if r[0] == label)
        monkeypatch.setattr(self._att(), "_pallas_available", lambda: True)
        decision = planner.plan(planner.PlanInputs(
            n_devices=1, platform="tpu", device_kind="TPU v5e",
            weights_bytes=10**9, batch=b, seq_len=sq, head_dim=d, heads=h,
        ))
        assert decision["attn"] == _route(sq, sk, d, b * h)._asdict()
        assert decision["attn"]["backend"] == backend
        assert (decision["attn"]["block_q"], decision["attn"]["block_k"]) == (
            blocks or (None, None))

    # The four PA_* variables that went with the measured table and the chunk
    # tuning, by suffix (so a grep for the full names finds no code).
    @pytest.mark.parametrize("suffix,value", [
        ("TUNING_PATH", "/nonexistent/tuning.json"),
        ("ATTN_CHUNK_TUNING", "/nonexistent/chunk.json"),
        ("ATTN_CHUNK_ELEMS", "1"),
        ("ATTN_BF16_SOFTMAX", "1"),
    ])
    def test_the_deleted_variables_are_read_by_nothing(self, monkeypatch,
                                                       suffix, value):
        att = self._att()
        shapes = [(4096, 4096, 40, 128), (1024, 1024, 64, 40),
                  (4173, 4173, 64, 48), (4608, 4608, 128, 24)]
        q, k, v = _qkv(b=2, sq=96, sk=64, h=2, d=16, seed=7)

        def chunked():
            with monkeypatch.context() as m:
                # several scan blocks: the threshold under the logits' size
                m.setattr(att, "_CHUNK_THRESHOLD", 2 * 2 * 64 * 16)
                return np.asarray(att._xla_chunked_attention(q, k, v, 0.25))

        routes = [att.resolve_route(*c) for c in shapes]
        out = chunked()
        monkeypatch.setenv(f"PA_{suffix}", value)
        assert [att.resolve_route(*c) for c in shapes] == routes
        np.testing.assert_array_equal(chunked(), out)


class TestFlashAttention:
    def test_interpret_is_never_a_silent_choice(self, monkeypatch):
        q, k, v = _qkv(b=1, sq=64, sk=64, h=2, d=32)
        # Off a TPU the caller must say what it wants.
        with pytest.raises(ValueError, match="pass interpret="):
            flash_attention(q, k, v)
        # On a TPU backend the kernel is compiled, never interpreted.
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(ValueError, match="never interprets on a TPU"):
            flash_attention(q, k, v, block_q=64, interpret=True)

    @pytest.mark.parametrize("sq,sk", [(64, 64), (100, 80), (256, 256), (300, 513)])
    def test_matches_xla(self, sq, sk):
        q, k, v = _qkv(b=1, sq=sq, sk=sk, h=2, d=32)
        got = flash_attention(q, k, v, block_q=128, block_k=128, interpret=True)
        want = _xla_attention(q, k, v, scale=32**-0.5)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )

    def test_batch_sharded_operands_run_under_the_callers_mesh(self, cpu_devices):
        # A Mosaic kernel in a jit with sharded operands is refused by the
        # partitioner on real chips ("cannot be automatically partitioned") —
        # found by the 4-chip smoke. Under the caller's context mesh
        # (parallel/mesh.mesh_context) the kernel is shard_mapped over the
        # data axis: rows stay where they are, nothing is gathered.
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from comfyui_parallelanything_tpu.parallel.mesh import mesh_context

        mesh = Mesh(np.array(cpu_devices[:4]), ("data",))
        q, k, v = _qkv(b=4, sq=128, sk=128, h=4, d=32)
        want = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
        sharded = [jax.device_put(a, NamedSharding(mesh, P("data")))
                   for a in (q, k, v)]
        fn = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, block_q=64, block_k=64, interpret=True))
        with mesh_context(mesh):
            got = fn(*sharded)
            hlo = fn.lower(*sharded).compile().as_text()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
        assert got.sharding.spec == P("data")
        assert "all-gather" not in hlo
        # A batch the axis does not divide runs the kernel as is.
        with mesh_context(mesh):
            odd = flash_attention(q[:3], k[:3], v[:3], block_q=64, block_k=64,
                                  interpret=True)
        np.testing.assert_allclose(np.asarray(odd), np.asarray(want[:3]),
                                   rtol=1e-6, atol=1e-6)

    def test_cross_attention_shape(self):
        q, k, v = _qkv(b=2, sq=32, sk=77, h=4, d=16)
        got = flash_attention(q, k, v, interpret=True)
        assert got.shape == (2, 32, 4, 16)

    def test_lane_padding_exact_at_unet_head_dim(self):
        # 40-dim SD1.5 heads run the kernel zero-padded to 128 lanes; padding
        # is EXACT (padded K dims add zero to every logit, padded V columns
        # emit discarded zeros), so the result must match plain attention at
        # the original dim — the property that makes padded routing safe.
        q, k, v = _qkv(b=2, sq=128, sk=128, h=2, d=40)
        got = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
        assert got.shape == (2, 128, 2, 40)
        want = _xla_attention(q, k, v, scale=40**-0.5)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )

    def test_long_sequence_many_k_blocks(self):
        # Video-length regime (scaled for interpreter mode): the k-block grid
        # dim walks 16 tiles; online-softmax state must stay exact across all
        # of them. On real TPU this shape runs with VMEM at O(block), not O(S).
        q, k, v = _qkv(b=1, sq=256, sk=4096, h=1, d=32)
        got = flash_attention(q, k, v, block_q=256, block_k=256, interpret=True)
        want = _xla_attention(q, k, v, scale=32**-0.5)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )

    def test_flash_under_sequence_parallel_ulysses(self, cpu_devices):
        # The composition the WAN long-context path uses on TPU: Ulysses
        # all_to_all head scatter inside shard_map, flash kernel as the local
        # attention. Forcing the pallas backend (interpret on CPU) proves the
        # kernel traces and runs inside the shard_map body.
        from comfyui_parallelanything_tpu.ops.attention import (
            get_attention_backend,
            set_attention_backend,
        )
        from comfyui_parallelanything_tpu.parallel.mesh import AXIS_SEQ, build_mesh
        from comfyui_parallelanything_tpu.parallel.sequence import (
            sequence_parallel_attention,
        )

        mesh = build_mesh(cpu_devices[:4], {AXIS_SEQ: 4})
        rng = np.random.default_rng(19)
        q = jnp.asarray(rng.normal(size=(1, 64, 4, 32)), jnp.float32)
        kv = jnp.asarray(rng.normal(size=(1, 64, 4, 32)), jnp.float32)
        want = _xla_attention(q, kv, kv, scale=32**-0.5)
        prev = get_attention_backend()
        set_attention_backend("pallas")
        try:
            got = sequence_parallel_attention(q, kv, kv, mesh, method="ulysses")
        finally:
            set_attention_backend(prev)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )

    def test_streamed_kv_block_invariance(self):
        # The k-block grid dimension streams K/V through VMEM; the result must be
        # independent of how the key sequence is tiled (VMEM stays O(block_k) even
        # at video lengths — the whole point of the streamed layout).
        q, k, v = _qkv(b=1, sq=128, sk=1000, h=1, d=32)
        fine = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
        coarse = flash_attention(q, k, v, block_q=128, block_k=512, interpret=True)
        want = _xla_attention(q, k, v, scale=32**-0.5)
        np.testing.assert_allclose(np.asarray(fine), np.asarray(want), rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(coarse), np.asarray(want), rtol=2e-4, atol=2e-4)

    def test_bf16(self):
        q, k, v = _qkv(b=1, sq=64, sk=64, h=1, d=32)
        q, k, v = (a.astype(jnp.bfloat16) for a in (q, k, v))
        got = flash_attention(q, k, v, interpret=True)
        assert got.dtype == jnp.bfloat16
        want = _xla_attention(q, k, v, scale=32**-0.5)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=5e-2, atol=5e-2
        )

    # The UNet classes with the shipped blocks (256 queries, a head group's
    # whole K and V up to 4096 keys, the softmax walking 2048 keys a tile)
    # scaled down 16x: 16 queries a block, 256 keys a block, 128 a tile.
    # (label, b, sq, sk, heads, head_dim)
    UNET_CLASSES = [
        ("sd15-4096x40", 2, 256, 256, 8, 40),      # 8 heads a group: 320 lanes
        ("sd15-1024x80", 2, 64, 64, 8, 80),        # one block, one tile
        ("sdxl-4096x64", 1, 256, 256, 10, 64),     # 2 heads a group, 5 groups
        ("sdxl-1024x64", 1, 64, 64, 20, 64),
        ("ragged-keys-40", 1, 48, 300, 4, 40),     # S_k not a multiple of block_k
        ("streamed-keys-64", 1, 32, 1000, 2, 64),  # 4 key blocks, masked tail
    ]

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("label,b,sq,sk,h,d", UNET_CLASSES,
                             ids=[c[0] for c in UNET_CLASSES])
    def test_unet_head_dims_match_xla(self, monkeypatch, label, b, sq, sk, h,
                                      d, dtype):
        import importlib

        fa = importlib.import_module(
            "comfyui_parallelanything_tpu.ops.pallas.flash_attention"
        )
        monkeypatch.setattr(fa, "_CHUNK_K", 128)
        q, k, v = (a.astype(dtype) for a in _qkv(b=b, sq=sq, sk=sk, h=h, d=d, seed=5))
        got = fa._flash_attention(q, k, v, scale=d ** -0.5, block_q=16,
                                  block_k=256, interpret=True)
        assert got.shape == q.shape and got.dtype == dtype
        want = _xla_attention(q, k, v, scale=d ** -0.5)
        tol = 2e-4 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)

    # A 128-wide-head row as ONE key block walked in several softmax tiles,
    # the tile shrunk from 2048 keys to 250 (a tile may then be 265 keys): 512
    # keys divide as 2 x 256 where three 250-key tiles would pad, as FLUX's
    # 4352 = 2 x 2176; 640 keys have no such split and run padded to 3 x 256,
    # the last 128 keys masked.
    # (label, keys, keys a block, keys a tile)
    ONE_BLOCK_ROWS = [("divides-exactly", 512, 512, 256),
                      ("padded-and-masked", 640, 768, 256)]

    @pytest.mark.parametrize("label,sk,block,tile", ONE_BLOCK_ROWS,
                             ids=[c[0] for c in ONE_BLOCK_ROWS])
    def test_a_row_as_one_key_block_in_several_tiles(self, monkeypatch, label,
                                                     sk, block, tile):
        import importlib

        fa = importlib.import_module(
            "comfyui_parallelanything_tpu.ops.pallas.flash_attention"
        )
        monkeypatch.setattr(fa, "_CHUNK_K", 250)
        assert fa.key_split(sk, sk) == (block, tile)
        q, k, v = _qkv(b=1, sq=96, sk=sk, h=2, d=128, seed=9)

        def run(q, k, v):
            return fa._flash_attention(q, k, v, scale=128 ** -0.5, block_q=32,
                                       block_k=sk, interpret=True)

        got = run(q, k, v)
        want = _xla_attention(q, k, v, scale=128 ** -0.5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        # One step of the key axis, no carried state; K and V are padded and
        # the keys masked only where the tiles do not divide the row.
        jaxpr = jax.make_jaxpr(run)(q, k, v)
        (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        assert call.params["grid_mapping"].grid[3] == 1
        assert not call.params["grid_mapping"].num_scratch_operands
        padded = block != sk
        pads = [e for e in jaxpr.eqns if e.params.get("name") == "_pad"]
        assert len(pads) == (2 if padded else 0)  # jnp.pad of K and of V
        assert ("iota" in str(call.params["jaxpr"])) == padded

    # The splits the routed classes run: the padded-dim and ragged rows' as
    # they were before the lane-aligned row had a rule (PR 33), and the new
    # row's. (label, keys, block asked for, keys a block, keys a tile)
    SPLITS = [
        ("padded-dim-4096", 4096, 4096, 4096, 2048),
        ("padded-dim-streamed-16384", 16384, 4096, 4096, 2048),
        ("ragged-4173", 4173, 4224, 4224, 1408),
        ("ragged-1152", 1152, 1152, 1152, 1152),
        ("ragged-1101", 1101, 1152, 1104, 1104),
        ("ragged-4300-pads-past-4352", 4300, 4352, 4608, 1536),
        ("flux-4352", 4352, 4352, 4352, 2176),
        ("flux-dev-4608", 4608, 4608, 4608, 1536),
        ("flux-512sq-1280", 1280, 1280, 1280, 1280),
        ("one-tile-2176", 2176, 2176, 2176, 2176),
        ("no-exact-split-2432", 2432, 2432, 2560, 1280),
        ("cross-77", 77, 256, 80, 80),
    ]

    @pytest.mark.parametrize("label,sk,asked,block,tile", SPLITS,
                             ids=[c[0] for c in SPLITS])
    def test_key_split(self, label, sk, asked, block, tile):
        from comfyui_parallelanything_tpu.ops.pallas.flash_attention import (
            key_split,
        )

        assert key_split(sk, asked) == (block, tile)
        assert block % tile == 0 and (block == tile or tile % 128 == 0)

    @pytest.mark.parametrize("heads,head_dim,group", [
        (8, 40, 8), (10, 64, 2), (20, 64, 2), (8, 80, 8), (8, 160, 4),
        (1, 512, 1), (24, 128, 1), (12, 128, 1), (5, 64, 5), (2, 32, 2),
    ])
    def test_head_groups_fill_whole_lane_tiles(self, heads, head_dim, group):
        # A block narrower than the (B, S, H·D) array must be a multiple of
        # 128 lanes wide; one as wide as the array may be anything.
        from comfyui_parallelanything_tpu.ops.pallas.flash_attention import (
            _head_group,
        )

        assert _head_group(heads, head_dim) == group
        assert group == heads or (group * head_dim) % 128 == 0

    def test_softmax_state_is_float32_and_operands_keep_their_dtype(self):
        # The stated precision: bfloat16 operands to both dots, float32
        # logits / max / sum / accumulator, probabilities cast to the operand
        # dtype for the second dot only — read off the kernel's jaxpr.
        import importlib

        fa = importlib.import_module(
            "comfyui_parallelanything_tpu.ops.pallas.flash_attention"
        )
        q, k, v = (a.astype(jnp.bfloat16) for a in _qkv(b=1, sq=64, sk=512, h=2, d=40))
        jaxpr = jax.make_jaxpr(lambda q, k, v: fa._flash_attention(
            q, k, v, scale=0.1, block_q=32, block_k=256, interpret=False))(q, k, v)
        (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]

        def eqns(jp):
            for e in jp.eqns:
                yield e
                for sub in jax.core.jaxprs_in_params(e.params):
                    yield from eqns(sub)

        seen = set()
        for e in eqns(call.params["jaxpr"]):
            name = e.primitive.name
            if name == "dot_general":
                assert [v.aval.dtype for v in e.invars] == [jnp.bfloat16] * 2
                assert e.outvars[0].aval.dtype == jnp.float32
            elif name in ("exp", "reduce_max", "reduce_sum", "max", "div"):
                assert e.outvars[0].aval.dtype == jnp.float32, name
            else:
                continue
            seen.add(name)
        assert seen >= {"dot_general", "exp", "reduce_max", "reduce_sum", "div"}
