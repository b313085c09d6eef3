"""End-to-end span tracing + observability satellites (round 8):

- utils/tracing.py: disabled-path no-op contract (no spans allocated, the
  null singleton, empty buffers), Chrome trace-event export validity
  (required keys, per-tid nesting), prompt correlation (span kwarg,
  inheritance, progress-scope fallback);
- utils/metrics.py histogram kind: Prometheus ``_bucket``/``_sum``/``_count``
  exposition (golden-text parse, label escaping, bucket monotonicity) and
  quantile read-side; scripts/loadgen.py's scraped-quantile twin;
- utils/logging.py ContextFilter: prompt_id/span_id stamped into records;
- serving + streaming instrumentation: lane-wait/step/lane spans on the
  submitter's timeline, stream-stage spans with overlap efficiency in (0,1];
- server GET /trace; scripts/trace_summary.py pinned against
  utils/tracing.trace_aggregates on the same fixture.
"""

from __future__ import annotations

import functools
import json
import logging
import re
import subprocess
import sys
import threading
import time
import urllib.request
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_parallelanything_tpu.utils import tracing
from comfyui_parallelanything_tpu.utils.logging import ContextFilter, get_logger
from comfyui_parallelanything_tpu.utils.metrics import MetricsRegistry, registry
from comfyui_parallelanything_tpu.utils.progress import progress_scope

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _tracer_clean():
    """Tracing is process-global: every test starts and ends disabled with a
    fresh buffer, so span leakage cannot couple tests."""
    tracing.disable()
    tracing.tracer.clear()
    yield
    tracing.disable()
    tracing.tracer.clear()


def _x_events(export=None, **kw):
    export = tracing.export(**kw) if export is None else export
    return [e for e in export["traceEvents"] if e.get("ph") == "X"]


def _assert_nested_per_tid(events):
    """Chrome X events on one tid must properly nest: sweeping by start time,
    every span is either contained in or disjoint from the open span above it
    (1 µs float-rounding slack)."""
    by_tid: dict[int, list] = {}
    for e in events:
        by_tid.setdefault(e["tid"], []).append(e)
    for tid, evs in by_tid.items():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack: list = []
        for e in evs:
            while stack and e["ts"] >= stack[-1]["ts"] + stack[-1]["dur"] - 1e-3:
                stack.pop()
            if stack:
                assert e["ts"] + e["dur"] <= (
                    stack[-1]["ts"] + stack[-1]["dur"] + 1.0
                ), f"tid {tid}: span {e} escapes parent {stack[-1]}"
            stack.append(e)


class TestTracerCore:
    def test_disabled_is_noop(self):
        """The tier-1 disabled-overhead contract: span() returns the shared
        null singleton (no Span allocated), record() writes nothing, no
        per-thread buffer is ever registered — the hot path is one flag
        check."""
        assert not tracing.on()
        s = tracing.span("anything", cat="x", foo=1)
        assert s is tracing._NULL
        assert tracing.span("other") is s  # the SAME object: nothing allocated
        with s as inner:
            assert inner is s
            inner.set(bar=2)  # attribute attach is a no-op too
        tracing.record("x", 0.0, 1.0, foo="bar")
        assert tracing.tracer._buffers == {}  # no buffer was ever touched
        assert _x_events() == []
        assert tracing.current_span_id() is None

    @pytest.mark.parametrize("path", ["sampler", "server-turn"])
    def test_disabled_hot_paths_allocate_no_spans(self, path, tmp_path,
                                                  monkeypatch):
        """An eager sampler run with tracing off must leave the tracer
        untouched — the instrumented hot paths (sampler-run wrapper, step
        callbacks) are all behind the single flag check. The same for a
        prompt's whole turn through the server (PR 36): the worker's
        ``worker-idle`` and ``prompt-finish``, the three spanned routes, the
        map of whose output a file is."""
        def no_span(*a, **kw):
            raise AssertionError("a span was built with the tracer off")

        monkeypatch.setattr(tracing, "_OpenSpan", no_span)
        if path == "sampler":
            from comfyui_parallelanything_tpu.sampling.runner import run_sampler

            def model(x, t, context=None, **kw):
                return x * 0.9

            noise = jnp.ones((1, 4, 4, 4))
            ctx = jnp.ones((1, 3, 8))
            out = run_sampler(model, noise, ctx, sampler="euler", steps=2)
            assert out.shape == noise.shape
        else:
            from comfyui_parallelanything_tpu.server import make_server

            srv, q = make_server(port=0, output_dir=str(tmp_path / "out"),
                                 class_mappings={"Save": _SaveNode},
                                 trace=False)
            threading.Thread(target=srv.serve_forever, daemon=True).start()
            try:
                base = f"http://127.0.0.1:{srv.server_address[1]}"
                _serve_prompt(base, q.output_dir, 1)
                assert _get(base, "/trace")["enabled"] is False
            finally:
                srv.shutdown()
                q.shutdown()
            assert q.output_owner == {}
            assert len(tracing.tracer._shared) == 0
        assert tracing.tracer._buffers == {}
        assert _x_events() == []

    def test_a_span_starts_where_the_one_it_follows_ended(self):
        """``start_us``: two spans that abut by definition share one clock
        reading; the null span has no end, and None reads the clock."""
        assert tracing._NULL.end is None
        tracing.enable()
        with tracing.span("first") as a:
            pass
        time.sleep(0.002)
        with tracing.span("second", start_us=a.end) as b:
            assert b.ts == a.end
        with tracing.span("third", start_us=None) as c:
            pass
        assert c.ts >= b.end > a.end
        first, second = (next(e for e in _x_events() if e["name"] == n)
                         for n in ("first", "second"))
        assert abs(first["ts"] + first["dur"] - second["ts"]) < 2e-3
        assert second["dur"] >= 2000.0

    @pytest.mark.parametrize("prompts, evicted", [(110, 0), (300, 44)])
    def test_retention_holds_a_window_of_the_fastest_cell(self, prompts,
                                                           evicted):
        """The completed-prompt tier keeps 256 prompts: the 110 that a 45 s
        window of the benchmark's fastest cell serves evict nothing (a
        smaller tier counts copies whose originals are all still in the
        rings); past its budget it counts every row it lets go."""
        tracing.enable()
        for i in range(prompts):
            tracing.record("prompt", 0.0, 1.0, prompt_id=f"p{i}")
            assert tracing.retain_prompt(f"p{i}") == 1
        assert tracing.tracer.dropped == (
            {"prompt-retention": evicted} if evicted else {})
        assert len(_x_events()) == prompts  # the ring still holds them all

    def test_export_shape_and_nesting(self):
        tracing.enable()
        with tracing.span("prompt", cat="server", prompt_id="p1"):
            with tracing.span("workflow-node", cat="graph", node="3"):
                with tracing.span("sampler-run", cat="sampling"):
                    pass
            with tracing.span("workflow-node", cat="graph", node="4"):
                pass
        trace = tracing.export()
        xs = _x_events(trace)
        assert len(xs) == 4
        for e in xs:
            for key in ("ph", "ts", "dur", "pid", "tid", "name"):
                assert key in e, (key, e)
            assert e["ph"] == "X" and e["dur"] >= 0
            # prompt correlation inherited down the whole subtree
            assert e["args"]["prompt_id"] == "p1"
        _assert_nested_per_tid(xs)
        # thread metadata present (Perfetto track naming)
        metas = [e for e in trace["traceEvents"] if e["ph"] == "M"]
        assert any(m["name"] == "thread_name" for m in metas)
        # the whole export is valid JSON for the Chrome trace loader
        json.loads(json.dumps(trace))

    def test_prompt_filter_and_cross_thread_record(self):
        tracing.enable()
        with tracing.span("prompt", prompt_id="keep"):
            time.sleep(0.001)
        with tracing.span("prompt", prompt_id="drop"):
            pass
        # dispatcher-style record onto another thread's tid
        done = threading.Event()
        main_tid = threading.get_ident()

        def dispatcher():
            t0 = tracing.now_us()
            tracing.record("step", t0, 5.0, cat="serving", tid=main_tid,
                           prompt_id="keep", lane=0)
            done.set()

        threading.Thread(target=dispatcher).start()
        assert done.wait(5)
        kept = _x_events(prompt_id="keep")
        assert {e["name"] for e in kept} == {"prompt", "step"}
        step = next(e for e in kept if e["name"] == "step")
        assert step["tid"] == main_tid  # landed on the prompt's timeline
        assert all(e["args"]["prompt_id"] == "keep" for e in kept)
        assert not any(
            e["args"].get("prompt_id") == "drop"
            for e in _x_events(prompt_id="keep")
        )

    def test_progress_scope_fallback(self):
        """A thread with no span context inherits its prompt from the
        per-thread progress scope — the server's correlation path."""
        tracing.enable()
        with progress_scope(prompt_id="scope-p"):
            assert tracing.current_prompt_id() == "scope-p"
            with tracing.span("workflow-node", cat="graph"):
                pass
            # nested scope without prompt_id stays on the same prompt
            with progress_scope(hook=lambda v, m: None):
                assert tracing.current_prompt_id() == "scope-p"
        [e] = _x_events()
        assert e["args"]["prompt_id"] == "scope-p"

    def test_ring_buffer_bounded(self):
        tracing.enable(capacity=16)
        for i in range(64):
            tracing.record("tick", float(i), 1.0)
        assert len(_x_events()) == 16  # old spans fell off, no growth


class TestHistogram:
    def test_exposition_golden_parse(self):
        """GET /metrics-shaped output must parse: TYPE lines, escaped labels,
        monotone cumulative buckets ending at +Inf == _count."""
        r = MetricsRegistry()
        labels = {"bucket": 'mo"del\nx', "lane": "0"}
        for v in (0.004, 0.004, 0.3, 7.0, 500.0):
            r.histogram("pa_t_step_seconds", v, labels=labels, help="t")
        r.counter("pa_t_total", 2, labels={"bucket": "b"})
        r.gauge("pa_t_gauge", 1.5)
        r.observe("pa_t_summary", 0.5)
        text = r.render()
        assert "# TYPE pa_t_step_seconds histogram" in text
        line_re = re.compile(
            r"^(# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .*"
            r"|[a-zA-Z_:][a-zA-Z0-9_:]*(\{([a-zA-Z_][a-zA-Z0-9_]*="
            r'"(\\.|[^"\\])*")(,[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*")*\})? '
            r"-?[0-9.eE+-]+(e[+-]?[0-9]+)?)$"
        )
        for line in text.strip().splitlines():
            assert line_re.match(line), f"unparseable exposition line: {line!r}"
        # bucket monotonicity + +Inf == _count
        buckets = re.findall(
            r'^pa_t_step_seconds_bucket\{[^}]*le="([^"]+)"[^}]*\} (\S+)$',
            text, re.M,
        )
        counts = [float(c) for _, c in buckets]
        assert counts == sorted(counts)
        assert buckets[-1][0] == "+Inf"
        count = float(re.search(
            r"^pa_t_step_seconds_count\{[^}]*\} (\S+)$", text, re.M
        ).group(1))
        assert counts[-1] == count == 5.0
        # raw newline/quote must not survive into the text unescaped
        assert 'mo\\"del\\nx' in text

    def test_explicit_bounds_first_touch_wins(self):
        """Round 15: a histogram may declare its bucket ladder at first
        touch (the SLO plane aligns edges to declared thresholds so a
        verdict is a bucket read); later bounds are ignored (one ladder per
        metric — exposition stays mergeable) and the default ladder is
        untouched for everyone else."""
        r = MetricsRegistry()
        r.histogram("pa_b_seconds", 0.2, bounds=(0.1, 0.25, 30.0, 60.0))
        r.histogram("pa_b_seconds", 31.0, bounds=(1.0, 2.0))  # ignored
        r.histogram("pa_b_seconds", 0.05, labels={"stage": "x"})
        text = r.render()
        # the declared ladder renders (threshold 30 an exact edge), for
        # EVERY label set of the metric
        for le in ("0.1", "0.25", "30", "60", "+Inf"):
            le_re = re.escape(le)
            assert re.search(
                rf'^pa_b_seconds_bucket\{{le="{le_re}"\}} ', text, re.M), le
            assert re.search(
                rf'^pa_b_seconds_bucket\{{stage="x",le="{le_re}"\}} ',
                text, re.M), le
        assert 'le="1"' not in text and 'le="2.5"' not in text
        # cumulative reads: 0.05 and 0.2 under 0.25; 31 lands in the 60
        # bucket (not +Inf)
        m = re.search(r'^pa_b_seconds_bucket\{le="0.25"\} (\S+)$', text, re.M)
        assert float(m.group(1)) == 1.0  # unlabeled set: only the 0.2
        # quantile rides the declared ladder
        assert 0.1 < r.quantile("pa_b_seconds", 40) <= 0.25
        assert r.quantile("pa_b_seconds", 99) <= 60.0
        # an untouched metric keeps the default ladder
        r.histogram("pa_default_seconds", 0.004)
        assert re.search(r'^pa_default_seconds_bucket\{le="0.001"\} ',
                         r.render(), re.M)

    def test_get_and_quantile(self):
        r = MetricsRegistry()
        for _ in range(99):
            r.histogram("h", 0.004)
        r.histogram("h", 40.0)
        s, c = r.get("h")
        assert c == 100 and s == pytest.approx(99 * 0.004 + 40.0)
        p50 = r.quantile("h", 50)
        assert 0.0025 < p50 <= 0.005  # inside the 0.004 bucket
        p95 = r.quantile("h", 95)
        assert p95 <= 0.005
        assert r.quantile("h", 99.9) > 25.0
        assert r.quantile("missing", 50) is None

    def test_loadgen_scraped_quantile_matches_registry(self):
        sys.path.insert(0, str(REPO / "scripts"))
        try:
            from loadgen import _histogram_quantile
        finally:
            sys.path.pop(0)
        r = MetricsRegistry()
        rng = np.random.default_rng(0)
        for v in rng.uniform(0.001, 2.0, size=200):
            r.histogram("pa_s_seconds", float(v), labels={"bucket": "b1"})
        for v in rng.uniform(0.001, 2.0, size=100):
            r.histogram("pa_s_seconds", float(v), labels={"bucket": "b2"})
        text = r.render()
        for q in (50, 95):
            scraped = _histogram_quantile(text, "pa_s_seconds", q)
            assert scraped == pytest.approx(r.quantile("pa_s_seconds", q))


class TestLoggingCorrelation:
    def _capture(self):
        logger = get_logger()
        records: list[str] = []

        class _Sink(logging.Handler):
            def emit(self, rec):
                records.append(self.format(rec))

        sink = _Sink()
        sink.setFormatter(logging.Formatter(
            "prompt=%(prompt_id)s span=%(span_id)s %(message)s"
        ))
        sink.addFilter(ContextFilter())
        logger.addHandler(sink)
        return logger, sink, records

    def test_records_stamped_from_span_context(self):
        tracing.enable()
        logger, sink, records = self._capture()
        try:
            logger.info("outside")
            with tracing.span("prompt", prompt_id="pX") as s:
                logger.info("inside")
                assert records[-1] == f"prompt=pX span={s.span_id} inside"
        finally:
            logger.removeHandler(sink)
        assert records[0] == "prompt=- span=- outside"

    def test_records_stamped_from_progress_scope(self):
        logger, sink, records = self._capture()
        try:
            with progress_scope(prompt_id="pScope"):
                logger.info("scoped")
        finally:
            logger.removeHandler(sink)
        assert records[-1] == "prompt=pScope span=- scoped"

    def test_default_handler_format_carries_correlation(self):
        logger = get_logger()
        fmt = logger.handlers[0].formatter._fmt
        assert "%(prompt_id)s" in fmt and "%(span_id)s" in fmt


def _tiny_model(x, t, context=None, **kw):
    c = jnp.mean(context, axis=tuple(range(1, context.ndim)))
    c = c.reshape((-1,) + (1,) * (x.ndim - 1))
    tt = t.reshape((-1,) + (1,) * (x.ndim - 1))
    return jnp.tanh(x * 0.9 + c * 0.1) * (0.5 + 0.1 * tt / 1000.0)


class TestServingSpans:
    def test_lane_wait_step_lane_on_submitter_timeline(self):
        from comfyui_parallelanything_tpu.sampling.runner import run_sampler
        from comfyui_parallelanything_tpu.serving import (
            ContinuousBatchingScheduler,
        )

        tracing.enable()
        sched = ContinuousBatchingScheduler(max_width=4, auto=False).install()
        try:
            tids = {}

            def worker(seed, steps):
                with tracing.span("prompt", prompt_id=f"p{seed}"):
                    tids[seed] = threading.get_ident()
                    r = np.random.default_rng(seed)
                    noise = jnp.asarray(
                        r.normal(size=(1, 8, 8, 4)).astype(np.float32))
                    ctx = jnp.asarray(
                        r.normal(size=(1, 6, 16)).astype(np.float32))
                    run_sampler(_tiny_model, noise, ctx, sampler="euler",
                                steps=steps)

            threads = [threading.Thread(target=worker, args=a, daemon=True)
                       for a in [(1, 2), (2, 3)]]
            for t in threads:
                t.start()
            t0 = time.time()
            while time.time() - t0 < 20:
                with sched._lock:
                    n = sum(len(b.queue) + len(b.active_lanes())
                            for b in sched.buckets.values())
                if n >= 2:
                    break
                time.sleep(0.005)
            sched.drain()
            for t in threads:
                t.join(20)
        finally:
            sched.uninstall()
            sched.shutdown()
        xs = _x_events()
        for seed, steps in [(1, 2), (2, 3)]:
            mine = [e for e in xs if e["args"].get("prompt_id") == f"p{seed}"]
            names = [e["name"] for e in mine]
            assert names.count("step") == steps, names
            assert "lane-wait" in names and "lane" in names
            # every span of this prompt sits on the submitter's own timeline,
            # even though the dispatcher thread recorded the serving ones
            assert {e["tid"] for e in mine} == {tids[seed]}
            _assert_nested_per_tid(mine)
        # dispatcher-side occupancy span carries the masked-lane count
        disp = [e for e in xs if e["name"] == "serving-dispatch"]
        assert disp and all(
            e["args"]["occupancy"] + e["args"]["masked_lanes"]
            == e["args"]["width"] for e in disp
        )
        # trace/metrics consistency: the histograms populated too
        text = registry.render()
        assert re.search(r"^pa_serving_step_seconds_bucket\{", text, re.M)
        assert re.search(r"^pa_serving_lane_wait_seconds_bucket\{", text, re.M)


class TestStreamingSpans:
    @pytest.fixture(scope="class")
    def flux_model(self):
        from comfyui_parallelanything_tpu.models.flux import (
            FluxConfig,
            build_flux,
        )

        cfg = FluxConfig(
            in_channels=16, hidden_size=64, num_heads=4, depth=2,
            depth_single_blocks=4, context_in_dim=32, vec_in_dim=16,
            axes_dim=(4, 6, 6), guidance_embed=False, dtype=jnp.float32,
        )
        return build_flux(
            cfg, jax.random.key(0), sample_shape=(1, 8, 8, 4), txt_len=16
        )

    @pytest.mark.parametrize("overlap", [True, False])
    def test_stream_stage_spans_and_overlap_efficiency(self, flux_model,
                                                       overlap):
        from comfyui_parallelanything_tpu.models.loader import params_nbytes
        from comfyui_parallelanything_tpu.parallel.streaming import (
            build_streaming_runner,
        )

        tracing.enable()
        runner = build_streaming_runner(
            flux_model.pipeline_spec, flux_model.params,
            jax.devices("cpu")[0],
            hbm_budget_bytes=params_nbytes(flux_model.params) // 3,
            overlap=overlap,
        )
        x = jnp.zeros((1, 8, 8, 4))
        t = jnp.ones((1,))
        ctx = jnp.zeros((1, 16, 32))
        y = jnp.zeros((1, 16))
        out = runner(x, t, ctx, y=y)
        jax.block_until_ready(out)
        xs = _x_events()
        names = {e["name"] for e in xs}
        assert {"stream-run", "stream-stage-prefetch",
                "stream-stage-compute"} <= names
        n_stages = runner.n_stages
        computes = [e for e in xs if e["name"] == "stream-stage-compute"]
        prefetches = [e for e in xs if e["name"] == "stream-stage-prefetch"]
        assert len(computes) == n_stages  # every stage's compute is spanned
        assert len(prefetches) == n_stages
        assert {e["args"]["stage"] for e in computes} == set(range(n_stages))
        assert all(e["args"]["nbytes"] > 0 for e in prefetches)
        # exposed transfer is booked separately from compute (the semantic
        # stream_overlap_efficiency depends on): one pre-dispatch wait per
        # stage, disjoint from every compute span
        waits = [e for e in xs if e["name"] == "stream-prefetch-wait"]
        assert {e["args"]["stage"] for e in waits} == set(range(n_stages))
        for w in waits:
            for c in computes:
                assert (w["ts"] + w["dur"] <= c["ts"] + 1.0
                        or w["ts"] >= c["ts"] + c["dur"] - 1.0), (w, c)
        eff = tracing.stream_overlap_efficiency(xs)
        assert eff is not None and 0.0 < eff <= 1.0
        _assert_nested_per_tid(xs)
        # the /metrics twin landed
        got = registry.get(
            "pa_stream_overlap_efficiency",
            {"device": str(jax.devices("cpu")[0])},
        )
        assert got is not None and 0.0 < got <= 1.0

    def test_no_spans_when_disabled(self, flux_model):
        from comfyui_parallelanything_tpu.models.loader import params_nbytes
        from comfyui_parallelanything_tpu.parallel.streaming import (
            build_streaming_runner,
        )

        runner = build_streaming_runner(
            flux_model.pipeline_spec, flux_model.params,
            jax.devices("cpu")[0],
            hbm_budget_bytes=params_nbytes(flux_model.params) // 3,
        )
        out = runner(jnp.zeros((1, 8, 8, 4)), jnp.ones((1,)),
                     jnp.zeros((1, 16, 32)), y=jnp.zeros((1, 16)))
        jax.block_until_ready(out)
        assert tracing.tracer._buffers == {}


class TestTraceSummaryScript:
    def _fixture_trace(self, tmp_path) -> Path:
        """A captured-fixture trace exercising every aggregate: one streamed
        run, serving lane-waits, and sequential steps with host gaps."""
        tracing.enable()
        t0 = tracing.now_us()
        tracing.record("stream-run", t0, 1000.0, cat="stream")
        tracing.record("stream-stage-prefetch", t0, 60.0, cat="stream",
                       stage=0, nbytes=100)
        tracing.record("stream-stage-compute", t0 + 100, 400.0, cat="stream",
                       stage=0, nbytes=100)
        tracing.record("stream-stage-compute", t0 + 550, 300.0, cat="stream",
                       stage=1, nbytes=100)
        tracing.record("lane-wait", t0, 2_000_000.0, cat="serving")
        tracing.record("lane-wait", t0, 1_000_000.0, cat="serving")
        with tracing.span("prompt", prompt_id="pf"):
            tracing.record("step", t0 + 2000, 100.0, cat="sampling", step=1)
            tracing.record("step", t0 + 2400, 100.0, cat="sampling", step=2)
        path = tmp_path / "trace.json"
        path.write_text(json.dumps(tracing.export()))
        return path

    def test_summary_matches_tracing_aggregates(self, tmp_path):
        path = self._fixture_trace(tmp_path)
        expect = tracing.trace_aggregates(tracing.export())
        proc = subprocess.run(
            [sys.executable, str(REPO / "scripts" / "trace_summary.py"),
             str(path), "--json"],
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stderr
        summary = json.loads(proc.stdout)
        # the stdlib re-implementation is pinned against the in-package math
        for key in ("stream_overlap_efficiency", "lane_wait_p95",
                    "host_gap_ms"):
            assert summary[key] == pytest.approx(expect[key]), key
        assert summary["stream_overlap_efficiency"] == pytest.approx(0.7)
        assert summary["lane_wait_p95"] == pytest.approx(2.0)
        assert summary["host_gap_ms"] == pytest.approx(0.3)
        assert summary["layers"]["stream"]["spans"] == 4
        assert summary["spans"] == len(_x_events())

    def test_human_output_and_prompt_filter(self, tmp_path):
        path = self._fixture_trace(tmp_path)
        proc = subprocess.run(
            [sys.executable, str(REPO / "scripts" / "trace_summary.py"),
             str(path)],
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stderr
        assert "stream_overlap_efficiency:" in proc.stdout
        proc = subprocess.run(
            [sys.executable, str(REPO / "scripts" / "trace_summary.py"),
             str(path), "--json", "--prompt-id", "pf"],
            capture_output=True, text=True, timeout=120,
        )
        summary = json.loads(proc.stdout)
        assert summary["spans"] == 3  # prompt span + its 2 steps
        assert summary["stream_overlap_efficiency"] is None


class _EchoNode:
    """Minimal declarative node for server round-trips without any model."""

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"x": ("INT", {"default": 0})}}

    RETURN_TYPES = ("INT",)
    FUNCTION = "run"

    def run(self, x):
        return (x + 1,)


class _SaveNode:
    """Writes one small file under ``dir`` and returns its path the way the
    SaveImage family does, so the history entry lists it and ``/view`` serves
    it."""

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"x": ("INT", {"default": 0}),
                             "dir": ("STRING", {"default": ""})}}

    RETURN_TYPES = ("STRING",)
    FUNCTION = "run"

    def run(self, x, dir):  # noqa: A002 — the graph's input name
        Path(dir).mkdir(parents=True, exist_ok=True)
        path = Path(dir) / f"echo_{x:05d}.png"
        path.write_bytes(b"\x89PNG" + bytes(64))
        return ([str(path)],)


class _BrokenNode:
    """Raises what ``how`` names: a node's own error, or the cooperative
    interrupt a Cancel turns into."""

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"how": ("STRING", {"default": "error"})}}

    RETURN_TYPES = ("INT",)
    FUNCTION = "run"

    def run(self, how):
        from comfyui_parallelanything_tpu.utils.progress import Interrupted

        raise Interrupted("stop") if how == "interrupted" else ValueError("boom")


@pytest.fixture
def server(tmp_path):
    from comfyui_parallelanything_tpu.server import make_server

    srv, q = make_server(
        port=0, output_dir=str(tmp_path / "out"),
        class_mappings={"Echo": _EchoNode, "Save": _SaveNode,
                        "Broken": _BrokenNode},
        trace=True,
    )
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    yield base, q
    srv.shutdown()
    q.shutdown()


def _get(base, path):
    """One call on a fresh connection, as the benchmark's client makes it."""
    with urllib.request.urlopen(base + path, timeout=30) as r:
        body, ctype = r.read(), r.headers.get("Content-Type", "")
    return json.loads(body) if "json" in ctype else body


def _post_prompt(base, graph) -> str:
    req = urllib.request.Request(
        base + "/prompt", data=json.dumps({"prompt": graph}).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read())["prompt_id"]


def _await_entry(base, pid, poll_s=0.05) -> dict:
    t0 = time.time()
    while time.time() - t0 < 60:
        hist = _get(base, f"/history/{pid}")
        if pid in hist:
            return hist[pid]
        time.sleep(poll_s)
    raise AssertionError(f"no history entry for {pid}")


def _await_span(base, pid, name) -> None:
    """A span is recorded where it closes: ``prompt-finish`` present means the
    worker's turn for ``pid`` is over — the history entry that a client polls
    for is written INSIDE that turn, so a fast client can be ahead of it."""
    t0 = time.time()
    while time.time() - t0 < 60:
        if any(e["name"] == name
               for e in _x_events(_get(base, f"/trace?prompt_id={pid}"))):
            return
        time.sleep(0.005)
    raise AssertionError(f"no {name} span for {pid}")


def _serve_prompt(base, out_dir, x) -> tuple[str, list]:
    """One request as the benchmark's client makes it: POST, poll until the
    entry is there, fetch every image back through /view."""
    pid = _post_prompt(base, {"1": {
        "class_type": "Save", "inputs": {"x": x, "dir": out_dir}}})
    entry = _await_entry(base, pid, poll_s=0.005)
    assert entry["status"]["status_str"] == "success"
    images = entry["outputs"]["1"]["images"]
    for ref in images:
        png = _get(base, f"/view?filename={ref['filename']}"
                         f"&subfolder={ref['subfolder']}")
        assert png.startswith(b"\x89PNG")
    return pid, images


class TestServerTraceEndpoint:
    def test_trace_endpoint_serves_prompt_timeline(self, server):
        base, q = server
        get = functools.partial(_get, base)
        pid = _post_prompt(base, {
            "1": {"class_type": "Echo", "inputs": {"x": 1}},
            "2": {"class_type": "Echo", "inputs": {"x": ["1", 0]}},
        })
        _await_entry(base, pid)
        trace = get(f"/trace?prompt_id={pid}")
        assert trace["enabled"] is True
        xs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
        names = [e["name"] for e in xs]
        assert names.count("prompt") == 1
        assert names.count("workflow-node") == 2  # both Echo nodes spanned
        prompt = next(e for e in xs if e["name"] == "prompt")
        for e in xs:
            assert e["args"]["prompt_id"] == pid
            # the worker's spans lie on its thread; the client's calls each
            # on the thread of their connection (PR 36)
            assert (e["tid"] == prompt["tid"]) != e["name"].startswith("http-")
        # admission-wait is written after the fact from the POST's enqueue
        # to the pickup, across worker-idle's end: it nests under nothing
        _assert_nested_per_tid(
            [e for e in xs if e["name"] != "admission-wait"])
        # unfiltered export includes it too; bogus filter excludes everything
        assert any(
            e.get("args", {}).get("prompt_id") == pid
            for e in get("/trace")["traceEvents"] if e.get("ph") == "X"
        )
        assert [e for e in get("/trace?prompt_id=nope")["traceEvents"]
                if e.get("ph") == "X"] == []


class TestServerTurnSpans:
    """PR 36: what lies between one ``prompt`` span and the next — the
    worker's ``prompt-finish`` and ``worker-idle``, and the handlers of the
    three routes a prompt's client calls."""

    TURN = ("prompt-finish", "worker-idle", "http-prompt", "http-history",
            "http-view")

    @pytest.fixture
    def two_prompts(self, server):
        base, q = server
        pids = []
        for x in (1, 2):
            pids.append(_serve_prompt(base, q.output_dir, x)[0])
            # the next POST is to fall inside the worker's wait, which opens
            # where this turn ends: wait for that, not for a while
            _await_span(base, pids[-1], "prompt-finish")
        # the worker stamps its wait with the NEXT prompt's id: a third POST
        # closes the span that follows the second prompt
        _await_entry(base, _post_prompt(
            base, {"1": {"class_type": "Echo", "inputs": {"x": 0}}}))
        return base, q, pids

    @pytest.mark.parametrize("name", TURN)
    def test_one_span_a_prompt_under_its_id(self, two_prompts, name):
        base, q, pids = two_prompts
        for pid in pids:
            xs = [e for e in _x_events(_get(base, f"/trace?prompt_id={pid}"))
                  if e["name"] == name]
            # the polls that missed left nothing: one http-history, the hit
            assert len(xs) == 1, (name, xs)
            e = xs[0]
            assert e["cat"] == "server" and e["dur"] > 0
            if name.startswith("http-"):
                assert e["args"]["status"] == 200 and e["args"]["bytes"] > 0

    def test_the_turn_is_covered_from_one_prompt_to_the_next(self, two_prompts):
        """``prompt`` closed to the next ``prompt`` opened, on the worker's
        thread: ``prompt-finish``, ``worker-idle`` and the tail of
        ``admission-wait`` leave none of it bare (ISSUE 36 allows 2 ms) —
        each starts on the clock reading its neighbour ended on, because the
        benchmark labels a whole idle gap by the one instant of its middle."""
        base, q, pids = two_prompts
        xs = _x_events(_get(base, "/trace"))
        prompts = sorted((e for e in xs if e["name"] == "prompt"),
                         key=lambda e: e["ts"])
        assert [e["args"]["prompt_id"] for e in prompts[:2]] == pids
        cover = sorted(
            (e["ts"], e["ts"] + e["dur"]) for e in xs
            if e["tid"] == prompts[0]["tid"] and e["name"] in (
                "prompt", "prompt-finish", "worker-idle", "admission-wait"))
        for a, b in zip(prompts, prompts[1:]):
            lo, hi = a["ts"] + a["dur"], b["ts"]
            bare, at = 0.0, lo
            for s0, s1 in cover:
                if s1 <= at or s0 >= hi:
                    continue
                bare += max(0.0, s0 - at)
                at = max(at, s1)
            bare += max(0.0, hi - at)
            assert bare < 1.0, (bare, hi - lo)  # microseconds: rounding
        # the client's calls for a prompt fall after its prompt span closed
        # (the POST of the next one inside the wait that carries ITS id)
        first = {e["name"]: e for e in xs
                 if e["args"].get("prompt_id") == pids[1]}
        idle, post = first["worker-idle"], first["http-prompt"]
        assert idle["ts"] <= post["ts"] < idle["ts"] + idle["dur"]
        done = prompts[1]["ts"] + prompts[1]["dur"]
        assert first["http-history"]["ts"] >= done
        assert first["http-view"]["ts"] >= first["http-history"]["ts"]

    def test_a_poll_that_misses_and_a_strange_file_record_nothing_to_a_prompt(
            self, server):
        base, q = server
        assert _get(base, "/history/nope") == {}
        Path(q.output_dir).mkdir(parents=True, exist_ok=True)
        (Path(q.output_dir) / "stray.png").write_bytes(b"\x89PNG")
        assert _get(base, "/view?filename=stray.png&subfolder=") == b"\x89PNG"
        for route in ("/queue", "/health", "/metrics", "/trace", "/history"):
            _get(base, route)
        xs = [e for e in _x_events(_get(base, "/trace"))
              if e["name"].startswith("http-")]
        # no span for the miss nor for the operator's routes; the file that
        # no prompt of this server wrote is served under no prompt's id
        assert [e["name"] for e in xs] == ["http-view"]
        assert "prompt_id" not in xs[0]["args"]

    @pytest.mark.parametrize("how", ["error", "interrupted"])
    def test_a_broken_prompt_closes_prompt_finish(self, server, how):
        base, q = server
        pid = _post_prompt(base, {"1": {
            "class_type": "Broken", "inputs": {"how": how}}})
        entry = _await_entry(base, pid, poll_s=0.005)
        assert entry["status"]["status_str"] == how
        # the next prompt's pickup shows the turn's end was reached
        _await_entry(base, _post_prompt(
            base, {"1": {"class_type": "Echo", "inputs": {"x": 0}}}))
        xs = _x_events(_get(base, f"/trace?prompt_id={pid}"))
        by = {e["name"]: e for e in xs}
        assert {"prompt", "prompt-finish", "http-prompt",
                "http-history"} <= set(by)
        # it starts on the reading `prompt` ended on (the export rounds each
        # number to a nanosecond)
        assert abs(by["prompt-finish"]["ts"]
                   - by["prompt"]["ts"] - by["prompt"]["dur"]) < 3e-3
        assert tracing.tracer.dropped == {}

    def test_prompt_finish_closes_when_the_finish_work_raises(self, server):
        """The history write itself failing kills the worker's turn, not the
        span: it is closed and recorded on the way out."""
        base, q = server

        def boom(prompt):
            raise RuntimeError("residency")

        q._mark_warm = boom  # raises inside the try: lands in history
        pid = _post_prompt(base, {"1": {"class_type": "Echo", "inputs": {"x": 3}}})
        assert _await_entry(base, pid)["status"]["status_str"] == "error"
        names = [e["name"] for e in _x_events(_get(base, f"/trace?prompt_id={pid}"))]
        assert names.count("prompt-finish") == 1
        # and past every handler: the span still closes, the stack is clean
        open_at_the_end = []

        def failing_status():
            open_at_the_end.extend(s.name for s in tracing.tracer._local.stack)
            raise ZeroDivisionError

        q._emit_status = failing_status
        with q._lock:
            q.pending_ids.append("direct")
        with pytest.raises(ZeroDivisionError):
            q._turn(("direct", {"1": {"class_type": "Echo", "inputs": {"x": 4}}},
                     False, 0, None, None, None, time.monotonic()))
        assert open_at_the_end == ["prompt-finish"]
        assert tracing.tracer._local.stack == []
        assert [e["name"] for e in _x_events(prompt_id="direct")].count(
            "prompt-finish") == 1

    def test_300_calls_on_fresh_connections_drop_no_span(self, server):
        """``http.server`` makes a thread a connection: the handlers' spans
        share one ring, so no thread registers one of its own and none is
        pushed off the retired ring (256 dead threads' worth)."""
        base, q = server
        pid, images = _serve_prompt(base, q.output_dir, 7)
        ref = images[0]
        before = set(tracing.tracer._buffers)
        for i in range(150):
            assert pid in _get(base, f"/history/{pid}")
            _get(base, f"/view?filename={ref['filename']}"
                       f"&subfolder={ref['subfolder']}")
        assert tracing.tracer.dropped == {}
        assert len(tracing.tracer._retired) == 0
        assert set(tracing.tracer._buffers) == before  # the worker's alone
        for _ in range(100):  # the last handler records after its reply
            xs = _x_events(_get(base, f"/trace?prompt_id={pid}"))
            names = [e["name"] for e in xs]
            if names.count("http-view") == 151:
                break
            time.sleep(0.01)
        assert names.count("http-history") == 151
        assert names.count("http-view") == 151
        assert len({e["args"]["span_id"] for e in xs}) == len(xs)
        assert (registry.get("pa_trace_dropped_total",
                             {"reason": "retired-ring"}) or 0.0) == 0.0


# -- PR 24: parents, the profiler bridge, denoise + save stage spans ---------


def _counting_model():
    """A ``DiffusionModel`` that counts the calls made INTO it, so spans and
    the counter can be held to the forwards the sampler really asked for."""
    from comfyui_parallelanything_tpu.models.api import DiffusionModel

    class Counting(DiffusionModel):
        calls = 0

        def __call__(self, *a, **kw):
            type(self).calls += 1
            return super().__call__(*a, **kw)

    return Counting(apply=lambda p, x, t, c=None, **kw: _tiny_model(x, t, c),
                    params={}, name="stub")


def _sample(model, sampler, steps, cfg=1.0, **kw):
    from comfyui_parallelanything_tpu.sampling.runner import run_sampler

    r = np.random.default_rng(0)
    noise = jnp.asarray(r.normal(size=(2, 8, 8, 4)).astype(np.float32))
    ctx = jnp.asarray(r.normal(size=(2, 6, 16)).astype(np.float32))
    return run_sampler(model, noise, ctx, sampler=sampler, steps=steps,
                       cfg_scale=cfg,
                       uncond_context=jnp.zeros_like(ctx) if cfg != 1.0 else None,
                       **kw)


def _denoiser_calls(program="model-apply:stub") -> float:
    return registry.get("pa_denoiser_calls_total",
                        {"program": program}) or 0.0


class _SampleNode:
    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"steps": ("INT", {"default": 3})}}

    RETURN_TYPES = ("LATENT",)
    FUNCTION = "run"

    def run(self, steps):
        return (_sample(_counting_model(), "euler", steps),)


class TestSpanTree:
    def test_graph_exports_the_chain_by_parent_span_id(self):
        from comfyui_parallelanything_tpu.host import run_workflow

        tracing.enable()
        with tracing.span("prompt", prompt_id="p"):
            run_workflow({"1": {"class_type": "Sample", "inputs": {"steps": 3}}},
                         class_mappings={"Sample": _SampleNode})
        xs = _x_events()
        by_id = {e["args"]["span_id"]: e for e in xs}
        denoise = [e for e in xs if e["name"] == "denoise"]
        assert len(denoise) == 3
        for d in denoise:
            chain, e = [], d
            while e is not None:
                chain.append(e["name"])
                e = by_id.get(e["args"].get("parent_span_id"))
            assert chain == ["denoise", "step", "sampler-run", "workflow-node",
                             "prompt"], chain
        # one denoise per step, each under a step of its own
        assert len({d["args"]["parent_span_id"] for d in denoise}) == 3
        roots = [e for e in xs if "parent_span_id" not in e["args"]]
        assert [e["name"] for e in roots] == ["prompt"]
        _assert_nested_per_tid(xs)

    def test_record_parents(self):
        """A span recorded on the calling thread hangs under the span open
        there; one recorded for another thread (``tid=``) names the span its
        caller captured at submission, and never the recorder's own."""
        tracing.enable()
        box = {}
        with tracing.span("submitter", prompt_id="p") as sub:
            box["parent"], box["tid"] = tracing.current_span_id(), threading.get_ident()
            tracing.record("inline", tracing.now_us(), 1.0)

            def dispatcher():
                with tracing.span("dispatcher-loop"):
                    tracing.record("lane", tracing.now_us(), 5.0, tid=box["tid"],
                                   prompt_id="p", parent_span_id=box["parent"])
                    tracing.record("orphan", tracing.now_us(), 5.0,
                                   tid=box["tid"], prompt_id="p")

            t = threading.Thread(target=dispatcher)
            t.start()
            t.join(10)
        args = {e["name"]: e["args"] for e in _x_events()}
        assert args["inline"]["parent_span_id"] == sub.span_id
        assert args["lane"]["parent_span_id"] == sub.span_id
        assert "parent_span_id" not in args["orphan"]

    def test_serving_spans_name_the_submitters_sampler_run(self):
        from comfyui_parallelanything_tpu.sampling.runner import run_sampler
        from comfyui_parallelanything_tpu.serving import (
            ContinuousBatchingScheduler,
        )

        tracing.enable()
        sched = ContinuousBatchingScheduler(max_width=2, auto=False).install()
        try:
            def worker():
                with tracing.span("prompt", prompt_id="p"):
                    run_sampler(_tiny_model, jnp.ones((1, 8, 8, 4)),
                                jnp.ones((1, 6, 16)), sampler="euler", steps=2)

            t = threading.Thread(target=worker, daemon=True)
            t.start()
            t0 = time.time()
            while time.time() - t0 < 20 and not any(
                    len(b.queue) + len(b.active_lanes())
                    for b in list(sched.buckets.values())):
                time.sleep(0.005)
            sched.drain()
            t.join(20)
        finally:
            sched.uninstall()
            sched.shutdown()
        xs = _x_events()
        run = next(e for e in xs if e["name"] == "sampler-run")
        lane = [e for e in xs if e["name"] in ("lane-wait", "lane", "step")]
        assert len(lane) == 4
        assert {e["args"]["parent_span_id"] for e in lane} == {run["args"]["span_id"]}


class TestDenoiseSite:
    @pytest.mark.parametrize("tracer_on", [True, False])
    @pytest.mark.parametrize("sampler,cfg,steps,forwards", [
        ("euler", 1.0, 4, 4), ("euler", 5.0, 4, 4),
        ("heun", 1.0, 4, 7), ("heun", 5.0, 3, 5),
        ("dpmpp_2m", 5.0, 5, 5),
    ])
    def test_spans_and_counter_equal_the_models_calls(
            self, sampler, cfg, steps, forwards, tracer_on):
        if tracer_on:
            tracing.enable()
        model = _counting_model()
        before = _denoiser_calls()
        _sample(model, sampler, steps, cfg)
        assert type(model).calls == forwards
        # the counter moves the same whether or not the tracer is on
        assert _denoiser_calls() - before == forwards
        xs = _x_events()
        if not tracer_on:
            assert xs == []
            return
        denoise = [e for e in xs if e["name"] == "denoise"]
        assert len(denoise) == forwards
        assert sum(e["name"] == "step" for e in xs) == steps
        assert all(e["args"]["program"] == "model-apply:stub"
                   and e["args"]["rows"] == (4 if cfg != 1.0 else 2)
                   and e["cat"] == "sampling" for e in denoise)

    def test_metrics_text_serves_the_counter_with_the_tracer_off(self):
        _sample(_counting_model(), "euler", 2)
        assert re.search(
            r'^pa_denoiser_calls_total\{program="model-apply:stub"\} \d',
            registry.render(), re.M)

    def test_parallel_model_is_one_site_of_its_own(self):
        import comfyui_parallelanything_tpu as pa

        tracing.enable()
        pm = pa.parallelize(
            (lambda p, x, t, context=None, **kw: _tiny_model(x, t, context), {}),
            pa.DeviceChain.even([f"cpu:{i}" for i in range(2)]))
        before = _denoiser_calls("parallel-apply")
        _sample(pm, "euler", 3)
        assert _denoiser_calls("parallel-apply") - before == 3
        denoise = [e for e in _x_events() if e["name"] == "denoise"]
        assert [e["args"]["program"] for e in denoise] == ["parallel-apply"] * 3

    def test_a_skipped_forward_shows_where_a_step_count_cannot(self, monkeypatch):
        """The guard the count of ``step`` spans could not give: a sampler
        that reuses the last x0 for one iteration and still fires every
        callback reads ``step`` = n and ``denoise`` = n - 1."""
        from comfyui_parallelanything_tpu.sampling import runner
        from comfyui_parallelanything_tpu.sampling.cfg import apply_callback

        def lazy_euler(denoise, x, sigmas, callback=None):
            x0 = None
            for i in range(len(sigmas) - 1):
                if i != 2:
                    x0 = denoise(x, sigmas[i])
                x = x + (x - x0) / sigmas[i] * (sigmas[i + 1] - sigmas[i])
                x = apply_callback(callback, i, x)
            return x

        monkeypatch.setitem(runner.K_SAMPLERS, "euler", lazy_euler)
        tracing.enable()
        _sample(_counting_model(), "euler", 5)
        names = [e["name"] for e in _x_events()]
        assert (names.count("step"), names.count("denoise")) == (5, 4)

    @pytest.mark.parametrize("fault", ["interrupt", "model-raises"])
    def test_a_broken_run_leaves_no_span_open(self, fault):
        """A step that never reaches its boundary is dropped with its
        annotation when ``sampler-run`` closes; the thread's stack is clean
        and only whole steps were recorded."""
        from comfyui_parallelanything_tpu.utils.progress import Interrupted

        tracing.enable()
        model = _counting_model()
        if fault == "interrupt":
            def hook(value, max_value):
                if value == 2:
                    raise Interrupted("stop")

            with progress_scope(hook=hook), pytest.raises(Interrupted):
                _sample(model, "euler", 4)
        else:
            real = type(model).__call__

            def failing(self, *a, **kw):
                if type(self).calls == 2:
                    raise RuntimeError("boom")
                return real(self, *a, **kw)

            type(model).__call__ = failing
            with pytest.raises(RuntimeError):
                _sample(model, "euler", 4)
        assert tracing.tracer._local.stack == []
        names = [e["name"] for e in _x_events()]
        # both faults strike after the second boundary: two whole steps were
        # recorded. The raising model leaves the third step open, and it is
        # counted as dropped; the interrupt strikes before the third opens.
        assert names.count("step") == 2 and names.count("sampler-run") == 1
        assert tracing.tracer.dropped.get("abandoned", 0) == (
            1 if fault == "model-raises" else 0)

    def test_a_span_left_twice_is_recorded_once(self):
        """``step`` is entered by hand and left from the callback: a sampler
        that fires a callback too many must not emit the same span again."""
        tracing.enable()
        with tracing.span("outer"):
            sp = tracing.span("step", step=1)
            sp.__enter__()
            sp.__exit__(None, None, None)
            sp.__exit__(None, None, None)
        names = [e["name"] for e in _x_events()]
        assert names.count("step") == 1 and names.count("outer") == 1
        assert tracing.tracer.dropped == {}

    def test_abandoned_spans_are_counted_not_recorded(self):
        before = registry.get("pa_trace_dropped_total",
                              {"reason": "abandoned"}) or 0.0
        tracing.enable()
        with tracing.span("outer"):
            tracing.span("left-open-1").__enter__()
            late = tracing.span("left-open-2")
            late.__enter__()
        # leaving it after its parent closed over it records nothing
        late.__exit__(None, None, None)
        assert [e["name"] for e in _x_events()] == ["outer"]
        assert tracing.tracer.dropped == {"abandoned": 2}
        assert registry.get("pa_trace_dropped_total",
                            {"reason": "abandoned"}) - before == 2

    def test_a_controlnet_composition_is_one_forward(self):
        """``apply_control`` merges the control trunk and the base into one
        program: one ``denoise`` and one count per step, under the
        composition's own program label."""
        from comfyui_parallelanything_tpu.models.api import DiffusionModel
        from comfyui_parallelanything_tpu.models.controlnet import apply_control

        base = DiffusionModel(
            apply=lambda p, x, t, c=None, control=None, **kw: _tiny_model(x, t, c),
            params={}, name="stub")
        ctrl = DiffusionModel(
            apply=lambda p, x, t, c=None, hint=None, y=None: {},
            params={}, name="ctrl")
        composed = apply_control(base, ctrl, jnp.zeros((64, 64, 3)))
        program = "model-apply:stub+control"
        tracing.enable()
        before = (_denoiser_calls(program), _denoiser_calls(),
                  _denoiser_calls("model-apply:ctrl"))
        _sample(composed, "euler", 3)
        assert (_denoiser_calls(program), _denoiser_calls(),
                _denoiser_calls("model-apply:ctrl")) == (
            before[0] + 3, before[1], before[2])
        denoise = [e for e in _x_events() if e["name"] == "denoise"]
        assert [e["args"]["program"] for e in denoise] == [program] * 3


class TestSaveStages:
    def _save(self, k, tmp_path):
        from comfyui_parallelanything_tpu.nodes import TPUSaveImage

        images = jnp.asarray(np.random.default_rng(1).uniform(
            size=(k, 16, 16, 3)).astype(np.float32))
        tracing.enable()
        with tracing.span("workflow-node", cat="graph", class_type="SaveImage",
                          prompt_id="p") as node:
            (paths,) = TPUSaveImage().save(images, output_dir=str(tmp_path))
        return images, paths, node

    @pytest.mark.parametrize("k", [1, 3])
    def test_save_splits_into_wait_fetch_and_one_encode(self, k, tmp_path):
        images, paths, node = self._save(k, tmp_path)
        xs = _x_events()
        parent = next(e for e in xs if e["name"] == "workflow-node")
        # the node's own spans (a first call's `compile` of the filter
        # program lies under `image-fetch`, in its own category)
        parts = [e for e in xs if e["cat"] == "graph" and e is not parent]
        assert sorted(e["name"] for e in parts) == [
            "device-wait", "image-fetch", "png-encode"]
        assert all(e["args"]["parent_span_id"] == node.span_id for e in parts)
        assert sum(e["dur"] for e in parts) <= parent["dur"]
        fetch = next(e for e in parts if e["name"] == "image-fetch")
        # the filtered bytes, not the floats: a filter byte and 16 RGB pixels a row
        assert fetch["args"]["bytes"] == k * 16 * (1 + 48)
        enc = next(e for e in parts if e["name"] == "png-encode")["args"]
        assert enc["images"] == k and enc["strips"] >= k and enc["threads"] >= 1
        assert enc["bytes"] == sum(Path(p).stat().st_size for p in paths)
        # what is written is what the unsplit node wrote
        from PIL import Image

        want = (np.clip(np.asarray(images), 0, 1) * 255.0 + 0.5).astype(np.uint8)
        for p, w in zip(paths, want):
            np.testing.assert_array_equal(np.asarray(Image.open(p)), w)

    def test_no_span_is_written_from_a_pool_thread(self, tmp_path, monkeypatch):
        """The strips deflate on the pool's threads; every span of the node is
        the prompt thread's, so summed spans read wall time, not thread time."""
        from comfyui_parallelanything_tpu.utils import png_encode

        ran_on = set()
        deflate = png_encode._deflate

        def spy(strip, last):
            ran_on.add(threading.get_ident())
            return deflate(strip, last)

        monkeypatch.setattr(png_encode, "_deflate", spy)
        self._save(3, tmp_path)
        assert ran_on and threading.get_ident() not in ran_on
        assert {e["tid"] for e in _x_events()} == {threading.get_ident()}
        assert not ran_on & set(tracing.tracer._buffers)


def _host_plane_events(log_dir) -> list:
    from jax.profiler import ProfileData

    (path,) = Path(log_dir).glob("plugins/profile/*/*.xplane.pb")
    data = ProfileData.from_file(str(path))
    return [(ev.name, dict(ev.stats)) for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


class TestProfilerBridge:
    OURS = ("workflow-node", "sampler-run", "step", "denoise")

    def _profiled_run(self, log_dir):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(log_dir), profiler_options=opts)
        try:
            with tracing.span("workflow-node", class_type="KSampler",
                              prompt_id="p"):
                _sample(_counting_model(), "euler", 3)
        finally:
            jax.profiler.stop_trace()
        return _host_plane_events(log_dir)

    def test_spans_lie_on_the_host_plane_under_a_profiler_session(self, tmp_path):
        tracing.enable()
        events = self._profiled_run(tmp_path)
        names = [n for n, _ in events]
        assert names.count("workflow-node") == 1 and names.count("step") == 3
        assert names.count("denoise") == 3
        # each carries the ids that tie it to the exported span
        exported = {e["args"]["span_id"]: e["name"] for e in _x_events()}
        for name, stats in events:
            if name in self.OURS:
                assert exported[int(stats["span_id"])] == name
                assert stats["prompt_id"] == "p"
        assert sorted(int(s["step_num"]) for n, s in events if n == "step") == [1, 2, 3]
        # beside the runtime's own events, on the same plane
        assert any(n.startswith("PjitFunction") for n in names)

    def test_tracer_off_builds_no_annotation(self, tmp_path, monkeypatch):
        def refuse(*a, **kw):
            raise AssertionError("an annotation was built with the tracer off")

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
        monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", refuse)
        events = self._profiled_run(tmp_path)
        assert not [n for n, _ in events if n in self.OURS]
        assert _x_events() == []


class TestEpochAnchor:
    def test_export_retakes_the_wall_anchor(self, monkeypatch):
        tracing.enable()
        with tracing.span("a"):
            pass
        first = tracing.export()
        assert abs(first["epoch_wall_s"] - time.time()) < 60
        # the wall clock steps (NTP) between enable() and export(): the anchor
        # follows the clock as it reads now, the stamp from enable() is gone
        real = time.time
        monkeypatch.setattr(tracing.time, "time", lambda: real() + 1000.0)
        moved = tracing.export()["epoch_wall_s"] - first["epoch_wall_s"]
        assert 999.0 < moved < 1001.0
