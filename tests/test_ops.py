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

    def test_bf16_softmax_env_matches_f32_at_bf16_tolerance(self, monkeypatch):
        # The sd15_16 MFU-budget lever: bf16 logits+softmax halves the chunked
        # path's HBM traffic; numerics must stay within bf16 tolerances.
        att = self._mod()
        q, k, v = _qkv(b=2, sq=96, sk=64, h=2, d=16, seed=7)
        monkeypatch.setattr(att, "_CHUNK_THRESHOLD", 2 * 2 * 64 * 16)
        ref = att._xla_chunked_attention(q, k, v, scale=16 ** -0.5)
        monkeypatch.setenv("PA_ATTN_BF16_SOFTMAX", "1")
        out = att._xla_chunked_attention(q, k, v, scale=16 ** -0.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-2, atol=2e-2)

    def test_chunk_elems_env_overrides_threshold(self, monkeypatch):
        att = self._mod()
        monkeypatch.setattr(att, "_RESOLVED", set())
        monkeypatch.setenv("PA_ATTN_CHUNK_ELEMS", "64")
        q, k, v = _qkv(b=1, sq=32, sk=32, h=2, d=8)
        att.attention_local(q, k, v)  # 2048 > 64 -> chunked
        assert att.resolved_backends() == ("xla_chunked",)
        assert att.chunk_config() == {
            "chunk_elems": 64, "bf16_softmax": False,
            # No degradation-ladder shrink in effect (round 14 evidence
            # labeling — a degraded process must not bank as configured).
            "degraded": False,
            # Per-field provenance: only the threshold came from the env.
            "sources": {"chunk_elems": "env", "bf16_softmax": "default"},
        }

    def test_persisted_chunk_tuning_honored(self, tmp_path, monkeypatch):
        # A chunk sweep persists the measured winner to the file
        # $PA_ATTN_CHUNK_TUNING names; a process pointed at it must serve it.
        import json as _json

        att = self._mod()
        path = tmp_path / "attn_chunk.json"
        path.write_text(_json.dumps(
            {"source": "measured", "chunk_elems": 128, "bf16_softmax": True}
        ))
        monkeypatch.setattr(att, "_CHUNK_TUNING_PATH", str(path))
        att._chunk_tuning.cache_clear()
        try:
            assert att._chunk_threshold() == 128
            assert att._softmax_dtype() == jnp.bfloat16
            cfg = att.chunk_config()
            assert cfg["sources"] == {"chunk_elems": "measured",
                                      "bf16_softmax": "measured"}
            assert cfg["chunk_elems"] == 128
            # Env still wins over the persisted table (the sweep itself).
            monkeypatch.setenv("PA_ATTN_CHUNK_ELEMS", "256")
            monkeypatch.setenv("PA_ATTN_BF16_SOFTMAX", "0")
            assert att._chunk_threshold() == 256
            assert att._softmax_dtype() == jnp.float32
        finally:
            att._chunk_tuning.cache_clear()

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

    @pytest.mark.parametrize("shape", [
        dict(sq=16, sk=16, d=4),      # 4 % 128 != 0: upstream has no lane pad
        dict(sq=40, sk=40, d=128),    # 40 % 128 != 0: upstream has no seq pad
        dict(sq=128, sk=72, d=128),   # mixed alignment is equally unservable
    ], ids=["padded-dim", "unaligned-seq", "unaligned-kv"])
    def test_forced_pallas_jax_raises_on_shapes_it_cannot_serve(self, shape):
        # A FORCED backend that cannot serve a shape raises; only "auto" may
        # choose another one.
        att = self._mod()
        att.set_attention_backend("pallas_jax")
        try:
            q, k, v = _qkv(b=1, h=1, **shape)
            with pytest.raises(ValueError, match="pallas_jax.*cannot serve"):
                att.attention_local(q, k, v)
            with pytest.raises(ValueError, match="pallas_jax.*cannot serve"):
                att.backend_plan(shape["sq"], shape["sk"], head_dim=shape["d"])
        finally:
            att.set_attention_backend("auto")


class TestKernelTuning:
    """Data-driven block sizes / backend choice (ops/pallas/tuning.py): the
    mechanism bench_kernels.py --apply feeds on real hardware."""

    def _table(self, entries):
        return {"source": "measured", "block_q": 256, "block_k": 256,
                "entries": entries}

    def test_defaults_without_file(self, monkeypatch):
        from comfyui_parallelanything_tpu.ops.pallas import tuning

        # No $PA_TUNING_PATH: the defaults, whatever an earlier run left in
        # the checkout.
        monkeypatch.setattr(tuning, "_PATH", None)
        tuning.kernel_tuning.cache_clear()
        try:
            assert tuning.best_blocks(4608) == (256, 256)
            assert tuning.pallas_wins(4608) is True  # default guess
            # A table that was asked for and cannot be read is an error.
            monkeypatch.setattr(tuning, "_PATH", "/nonexistent/tuning.json")
            tuning.kernel_tuning.cache_clear()
            with pytest.raises(OSError):
                tuning.kernel_tuning()
        finally:
            tuning.kernel_tuning.cache_clear()

    def test_measured_entries_drive_choice(self, monkeypatch):
        from comfyui_parallelanything_tpu.ops.pallas import tuning

        table = self._table([
            {"seq": 4608, "block_q": 512, "block_k": 256,
             "pallas_ms": 1.0, "xla_ms": 2.0},
            {"seq": 512, "block_q": 128, "block_k": 128,
             "pallas_ms": 3.0, "xla_ms": 1.0},  # kernel LOSES at short seq
        ])
        monkeypatch.setattr(tuning, "kernel_tuning", lambda: {**tuning._DEFAULT, **table})
        assert tuning.best_blocks(4000) == (512, 256)  # nearest: 4608
        assert tuning.best_blocks(600) == (128, 128)
        assert tuning.pallas_wins(4608) is True
        assert tuning.pallas_wins(384) is False  # nearest entry says xla

    def test_xla_oom_entry_counts_as_pallas_win(self, monkeypatch):
        # An entry whose XLA measurement failed (S×S logits OOM at video
        # lengths) marks a length where the fused kernel is MANDATORY.
        from comfyui_parallelanything_tpu.ops.pallas import tuning

        table = self._table([
            {"seq": 4608, "block_q": 256, "block_k": 256,
             "pallas_ms": 2.0, "xla_ms": 1.5},        # xla narrowly wins
            {"seq": 32768, "block_q": 256, "block_k": 512,
             "pallas_ms": 40.0, "xla_ms": None},      # xla OOMed
        ])
        monkeypatch.setattr(
            tuning, "kernel_tuning", lambda: {**tuning._DEFAULT, **table}
        )
        assert tuning.pallas_wins(32768) is True   # never route 32k to xla
        assert tuning.pallas_wins(4608) is False

    def test_foreign_device_table_ignored(self, monkeypatch, tmp_path):
        # A v5e-measured table must not apply on a different TPU generation.
        import json as _json

        from comfyui_parallelanything_tpu.ops.pallas import tuning

        p = tmp_path / "tuning.json"
        p.write_text(_json.dumps({
            "device_kind": "TPU v99", "block_q": 512, "block_k": 512,
            "entries": [{"seq": 128, "block_q": 512, "block_k": 512,
                         "pallas_ms": 9.0, "xla_ms": 1.0}],
        }))
        monkeypatch.setattr(tuning, "_PATH", str(p))
        tuning.kernel_tuning.cache_clear()
        try:
            assert tuning.kernel_tuning()["source"] == "default"
            assert tuning.best_blocks(128) == (256, 256)
        finally:
            tuning.kernel_tuning.cache_clear()

    def test_write_and_reload_roundtrip(self, monkeypatch, tmp_path):
        from comfyui_parallelanything_tpu.ops.pallas import tuning

        monkeypatch.setattr(tuning, "_PATH", str(tmp_path / "tuning.json"))
        tuning.kernel_tuning.cache_clear()
        try:
            import jax

            kind = jax.devices()[0].device_kind  # must match to be applied
            tuning.write_tuning({
                "device_kind": kind,
                "block_q": 512, "block_k": 128,
                "entries": [{"seq": 16384, "block_q": 512, "block_k": 128,
                             "pallas_ms": 5.0, "xla_ms": 50.0}],
            })
            t = tuning.kernel_tuning()
            assert t["source"] == "measured" and t["device_kind"] == kind
            assert tuning.best_blocks(20000) == (512, 128)
        finally:
            tuning.kernel_tuning.cache_clear()

    def test_padded_head_dim_gate(self, monkeypatch):
        # Non-128-aligned head dims (40/64/80 UNet heads) are routed by the
        # shape rule (tuning.padded_dim_route: key length and B·H·S_q·S_k,
        # set from the v5e measurements beside it), not by a default "no":
        # aligned dims keep the default-True guess; a table measured at that
        # very dim overrides the rule for its class.
        from comfyui_parallelanything_tpu.ops.pallas import tuning

        monkeypatch.setattr(
            tuning, "kernel_tuning", lambda: {**tuning._DEFAULT, "entries": []}
        )
        assert tuning.pallas_wins(16384, 128) is True   # aligned: default guess
        assert tuning.pallas_wins(16384, 40) is True    # rule: long keys
        assert tuning.pallas_wins(4096, 40, seq_k=77) is False  # cross-attn
        assert tuning.pallas_wins(256, 160) is False    # short inner level
        # B·H·S_q·S_k under 2^27 (SDXL's 1024-token class) lost on the chip.
        assert tuning.pallas_wins(1024, 64, batch_heads=40) is False
        assert tuning.pallas_wins(1024, 80, batch_heads=128) is True
        assert tuning.best_blocks(4096, 40) == tuning.PADDED_DIM_BLOCKS
        assert tuning.best_blocks(4096, 40, seq_k=77) == (256, 256)

        table = self._table([
            {"seq": 16384, "head_dim": 40, "block_q": 512, "block_k": 256,
             "pallas_ms": 100.0, "xla_ms": 180.0},      # padded kernel wins
            {"seq": 4096, "head_dim": 64, "block_q": 256, "block_k": 256,
             "pallas_ms": 9.0, "xla_ms": 4.0},          # padded kernel loses
            {"seq": 4608, "block_q": 256, "block_k": 256,
             "pallas_ms": 1.0, "xla_ms": 2.0},          # aligned (no dim tag)
        ])
        monkeypatch.setattr(
            tuning, "kernel_tuning", lambda: {**tuning._DEFAULT, **table}
        )
        assert tuning.pallas_wins(16384, 40) is True
        assert tuning.pallas_wins(4096, 64) is False    # measured loss wins
        # Aligned queries must not be judged by padded-dim entries.
        assert tuning.pallas_wins(4608, 128) is True
        # Same-dim measurements drive block choice for that class.
        assert tuning.best_blocks(16384, 40) == (512, 256)
        assert tuning.best_blocks(4608, 128) == (256, 256)
        # A measured padded-dim entry speaks for at most 2x in seq either
        # way; beyond that the rule decides: 256 tokens stay on XLA.
        assert tuning.pallas_wins(256, 40) is False
        assert tuning.pallas_wins(8192, 40) is True  # within 2x of 16384

    def test_padded_dim_blocks_never_inherit_aligned_winners(self, monkeypatch):
        # ADVICE r3: best_blocks for a padded dim with NO same-dim entry must
        # never return blocks tuned for another dim class: the shape rule's
        # where it routes, the defaults where a forced pallas backend runs a
        # shape the rule leaves to XLA.
        from comfyui_parallelanything_tpu.ops.pallas import tuning

        table = self._table([
            {"seq": 4608, "head_dim": 128, "block_q": 512, "block_k": 512,
             "pallas_ms": 1.0, "xla_ms": 2.0},
        ])
        monkeypatch.setattr(
            tuning, "kernel_tuning", lambda: {**tuning._DEFAULT, **table}
        )
        assert tuning.best_blocks(4608, head_dim=40) == tuning.PADDED_DIM_BLOCKS
        assert tuning.best_blocks(512, head_dim=40) == (256, 256)
        assert tuning.best_blocks(4608, head_dim=128) == (512, 512)

    def test_fused_backend_picks_measured_winner(self, monkeypatch):
        # Two fused candidates (in-repo kernel vs jax's upstream one): auto
        # routes to whichever measured faster; padded dims always take the
        # in-repo kernel (upstream has no lane padding); a shape where ONLY
        # the upstream kernel produced a number (round-3's hang scenario)
        # still counts as a fused win over XLA.
        from comfyui_parallelanything_tpu.ops.pallas import tuning

        table = self._table([
            {"seq": 4608, "head_dim": 128, "block_q": 256, "block_k": 256,
             "pallas_ms": None, "pallas_jax_ms": 3.0, "xla_ms": 9.0},
            {"seq": 16384, "head_dim": 128, "block_q": 256, "block_k": 256,
             "pallas_ms": 2.0, "pallas_jax_ms": 4.0, "xla_ms": 9.0},
        ])
        monkeypatch.setattr(
            tuning, "kernel_tuning", lambda: {**tuning._DEFAULT, **table}
        )
        assert tuning.fused_backend(4608, 128) == "pallas_jax"
        assert tuning.fused_backend(16384, 128) == "pallas"
        assert tuning.fused_backend(4608, 40) == "pallas"  # padded dim
        assert tuning.pallas_wins(4608, 128) is True  # jax-kernel-only entry
        # No measurements at all: default to the in-repo kernel.
        monkeypatch.setattr(tuning, "kernel_tuning", lambda: dict(tuning._DEFAULT))
        assert tuning.fused_backend(4608, 128) == "pallas"

    def test_aligned_blocks_ignore_padded_dim_entries(self, monkeypatch):
        # A partial sweep can leave ONLY padded-dim entries (per-shape
        # subprocess timeouts); aligned dims must then fall back to defaults,
        # not adopt blocks tuned under the padded-FLOP regime.
        from comfyui_parallelanything_tpu.ops.pallas import tuning

        table = self._table([
            {"seq": 16384, "head_dim": 40, "block_q": 512, "block_k": 512,
             "pallas_ms": 100.0, "xla_ms": 180.0},
        ])
        monkeypatch.setattr(
            tuning, "kernel_tuning", lambda: {**tuning._DEFAULT, **table}
        )
        assert tuning.best_blocks(4608, 128) == (256, 256)  # defaults
        assert tuning.pallas_wins(4608, 128) is True        # default guess

    def test_auto_backend_respects_measured_loss(self, monkeypatch):
        # Auto mode must fall back to XLA for lengths where measurement says
        # the fused kernel loses — even on TPU with aligned shapes.
        import importlib

        # ops/__init__ rebinds the name `attention` to the function, shadowing
        # the submodule on attribute access — resolve the module explicitly.
        att = importlib.import_module("comfyui_parallelanything_tpu.ops.attention")
        from comfyui_parallelanything_tpu.ops.pallas import tuning

        calls = []
        monkeypatch.setattr(att, "_pallas_available", lambda: True)
        monkeypatch.setattr(
            tuning, "kernel_tuning",
            lambda: {**tuning._DEFAULT, "entries": [
                {"seq": 128, "block_q": 128, "block_k": 128,
                 "pallas_ms": 9.0, "xla_ms": 1.0},
            ]},
        )
        fa = importlib.import_module(
            "comfyui_parallelanything_tpu.ops.pallas.flash_attention"
        )
        real = fa.flash_attention
        monkeypatch.setattr(
            fa, "flash_attention",
            lambda *a, **kw: calls.append(kw) or real(*a, interpret=True, **kw),
        )
        q = jnp.ones((1, 128, 2, 128), jnp.float32)
        out = att.attention_local(q, q, q)
        assert out.shape == q.shape
        assert calls == []  # measured loss -> xla path, kernel never invoked


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
