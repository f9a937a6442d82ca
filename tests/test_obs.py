"""Observability subsystem: tracer, metrics registry, numerics monitors,
and their wiring through the serving engine.

Covers the PR-6 contracts:
  * trace validity — emitted JSON parses as Chrome/Perfetto trace_event,
    spans nest strictly per thread, compile instants present;
  * golden-key schemas — ``engine.metrics()`` and ``registry.snapshot()``
    key sets are frozen so BENCH_serve.json rows can't drift silently;
  * zero-elapsed guards — ``decode_tok_per_s``/``prefill_tok_per_s`` report
    0.0 (not inf) when the steady-state timers never accumulated;
  * ``reset_metrics()`` resets every request-level series (TTFT samples,
    preemption counter, queue-wait histogram) with the registry;
  * queue observability under pool pressure — preemption counter,
    queue-wait histogram and queue-depth gauge move;
  * numerics — the cond monitor flags the cond=1e9 fixture from
    test_dist_calibrate while staying silent on well-conditioned layers,
    single-device and sharded (subprocess with 8 fake devices).
"""
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.obs import metrics, numerics, trace
from repro.serve import ContinuousEngine

from test_dist_calibrate import run_with_devices


@pytest.fixture(scope="module")
def smollm():
    cfg = get_smoke_config("smollm_135m")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(autouse=True)
def _tracing_off():
    """Every test starts and ends with the process tracer uninstalled."""
    trace.disable()
    yield
    trace.disable()


def _engine(model, params, **kw):
    kw.setdefault("compute_dtype", jnp.float32)
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("max_running", 4)
    return ContinuousEngine(model, params, **kw)


def _prompt(cfg, n, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)


def _ill_conditioned_r(n=16, k=64, cond=1e9, seed=0):
    """Upper-triangular R of an (k, n) X with the given condition number —
    the same logspace-singular-value fixture test_dist_calibrate uses."""
    rng = np.random.RandomState(seed)
    u, _ = np.linalg.qr(rng.standard_normal((k, n)))
    v, _ = np.linalg.qr(rng.standard_normal((n, n)))
    s = np.logspace(0, -np.log10(cond), n)
    x = u @ np.diag(s) @ v.T
    return np.linalg.qr(x, mode="r").astype(np.float32)


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------

class TestTracer:
    def test_disabled_is_shared_noop(self):
        assert not trace.enabled()
        assert trace.span("a") is trace.span("b", x=1)
        trace.instant("nothing")                 # no-op, no error
        assert trace.save("/tmp/unused.json") == 0

    def test_span_and_instant_events(self, tmp_path):
        trace.enable()
        with trace.span("outer", a=1):
            with trace.span("inner"):
                pass
            trace.instant("tick", s=2)
        path = tmp_path / "t.json"
        assert trace.save(str(path)) == 3
        doc = json.loads(path.read_text())
        evs = [e for e in doc["traceEvents"] if e.get("ph") != "M"]
        by_name = {e["name"]: e for e in evs}
        assert by_name["inner"]["ph"] == "X"
        assert by_name["tick"]["ph"] == "i"
        # inner completes before outer and lies inside it
        out, inn = by_name["outer"], by_name["inner"]
        assert out["ts"] <= inn["ts"]
        assert inn["ts"] + inn["dur"] <= out["ts"] + out["dur"] + 1e-6
        assert out["args"] == {"a": 1}

    def test_thread_safety_and_per_thread_tids(self):
        t = trace.enable()

        barrier = threading.Barrier(4)     # idents are reused after a
                                           # thread exits; keep all 4 alive

        def work(i):
            barrier.wait()
            for _ in range(50):
                with trace.span(f"w{i}"):
                    pass

        threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        evs = t.events()
        assert len(evs) == 200
        assert len({e["tid"] for e in evs}) == 4

    def test_enable_idempotent_disable_drops(self):
        t1 = trace.enable()
        t2 = trace.enable()
        assert t1 is t2 and trace.current() is t1
        trace.disable()
        assert trace.current() is None

    def test_ring_mode_bounds_memory(self):
        """enable(max_events=N) keeps the most recent N events and counts
        the overflow in dropped; save() still emits valid JSON."""
        t = trace.enable(max_events=10)
        for i in range(25):
            t.instant(f"e{i}")
        evs = t.events()
        assert len(evs) == 10
        assert [e["name"] for e in evs] == [f"e{i}" for i in range(15, 25)]
        assert t.dropped == 15
        assert [e["name"] for e in t.tail(3)] == ["e22", "e23", "e24"]

    def test_ring_recap_in_place(self):
        """Re-enabling with an explicit cap re-caps the live tracer,
        keeping the newest events."""
        t = trace.enable()
        for i in range(8):
            t.instant(f"e{i}")
        assert trace.enable(max_events=3) is t
        assert [e["name"] for e in t.events()] == ["e5", "e6", "e7"]
        assert t.dropped == 5
        t.instant("e8")
        assert [e["name"] for e in t.events()] == ["e6", "e7", "e8"]


def _profile_host_events(logdir):
    """(name, stats) of every event on the profiler trace's host planes."""
    import glob
    found = glob.glob(str(logdir / "**" / "*.xplane.pb"), recursive=True)
    assert found, f"no .xplane.pb under {logdir}"
    pd = jax.profiler.ProfileData.from_file(found[0])
    return [(e.name, dict(e.stats)) for plane in pd.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


class TestProfilerSpans:
    """Spans follow a ``jax.profiler`` session onto its host plane and into
    ``trace.spans``, with nothing recorded while neither is on."""

    def test_span_reaches_profiler_and_spans(self, tmp_path):
        lo = time.perf_counter()
        jax.profiler.start_trace(str(tmp_path))
        try:
            with trace.span("outer", rows=3) as sp:
                with trace.span("inner", sig=(4, 8, True)):
                    jnp.ones(4).block_until_ready()
                sp.set(finished=1)
        finally:
            jax.profiler.stop_trace()
        hi = time.perf_counter()
        assert not trace.enabled()          # recorded in the profiler's ring
        got = {s.name: s for s in trace.spans(lo, hi)}
        assert set(got) == {"outer", "inner"}
        out, inn = got["outer"], got["inner"]
        assert inn.parent == out.id and out.parent is None
        assert out.start <= inn.start <= inn.end <= out.end
        assert out.args == {"rows": 3, "finished": 1}
        assert inn.args == {"sig": (4, 8, True)}
        host = dict(_profile_host_events(tmp_path))
        assert host["outer"] == {"rows": 3, "finished": 1}
        assert host["inner"] == {"sig": "(4, 8, True)"}
        # once the session ends, spans are free again
        assert trace.span("after") is trace.span("after2")
        assert [s.name for s in trace.spans(lo, time.perf_counter())] == \
            ["inner", "outer"]

    def test_off_records_nothing(self):
        lo = time.perf_counter()
        with trace.span("a", rows=1) as sp:
            sp.set(finished=2)
        trace.complete("serve.queue_wait", lo, req_id=1)
        trace.instant("tick")
        assert trace.span("b") is trace.span("c")
        assert trace.spans(lo, time.perf_counter()) == []
        assert trace.current() is None

    def test_complete_event_and_raw_args_export(self, tmp_path):
        t = trace.enable()
        start = time.perf_counter()
        with trace.span("serve.decode_step", rows=2, sig=(2, 4, True)):
            pass
        trace.complete("serve.queue_wait", start, start + 0.25, req_id=7)
        q = [s for s in trace.spans(start, start + 1.0)
             if s.name == "serve.queue_wait"]
        assert len(q) == 1 and q[0].end - q[0].start == 0.25
        assert q[0].parent is None and q[0].args == {"req_id": 7}
        # an interval may overlap others on its thread: it is exported as
        # an async begin/end pair, not as a complete event
        path = tmp_path / "t.json"
        assert trace.save(str(path)) == 3
        evs = {(e["name"], e["ph"]): e for e in json.loads(
            path.read_text())["traceEvents"]}
        assert evs["serve.decode_step", "X"]["args"] == {
            "rows": 2, "sig": "(2, 4, True)"}
        b, e = evs["serve.queue_wait", "b"], evs["serve.queue_wait", "e"]
        assert b["id"] == e["id"] and b["args"] == {"req_id": 7}
        assert b["ts"] == pytest.approx((start - t._t0) * 1e6)
        assert e["ts"] - b["ts"] == pytest.approx(2.5e5)

    def test_window_past_a_dropped_event_is_unreadable(self):
        trace.enable(max_events=4)
        marks = []
        for i in range(10):
            marks.append(time.perf_counter())
            with trace.span(f"s{i}"):
                pass
        assert trace.current().dropped == 6
        assert trace.spans(marks[0], marks[-1]) is None
        late = trace.spans(marks[7], time.perf_counter())
        assert [s.name for s in late] == ["s7", "s8", "s9"]


def _nesting_ok(events):
    """Per-tid, complete events must nest like a call stack: sorted by
    start, each next span either starts after the top ends (pop) or lies
    entirely inside it (push)."""
    by_tid = {}
    for e in events:
        if e.get("ph") == "X":
            by_tid.setdefault(e["tid"], []).append(e)
    for evs in by_tid.values():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []
        for e in evs:
            while stack and e["ts"] >= stack[-1]["ts"] + stack[-1]["dur"]:
                stack.pop()
            if stack and e["ts"] + e["dur"] > \
                    stack[-1]["ts"] + stack[-1]["dur"] + 1e-3:
                return False                     # overlap without containment
            stack.append(e)
    return True


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counter_gauge_histogram(self):
        reg = metrics.Registry()
        c = reg.counter("x_total")
        c.inc(); c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)
        g = reg.gauge("depth", fn=lambda: 42)
        assert g.value == 42
        with pytest.raises(ValueError):
            g.set(3)                             # callback-backed
        h = reg.histogram("lat_seconds", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.5, 5.0):
            h.observe(v)
        assert h.count == 4 and h.max == 5.0
        assert h.quantile(0.5) == 0.1
        assert h.quantile(1.0) == 5.0            # overflow capped at max

    def test_strict_registration(self):
        reg = metrics.Registry()
        reg.counter("a_total")
        with pytest.raises(ValueError):
            reg.counter("a_total")               # duplicate
        with pytest.raises(ValueError):
            reg.gauge("bad name")                # illegal chars
        with pytest.raises(ValueError):
            reg.histogram("h", buckets=(1.0, 0.5))   # not increasing

    def test_log_buckets(self):
        b = metrics.log_buckets(1e-3, 1.0, per_decade=1)
        assert b[0] == pytest.approx(1e-3)
        assert b[-1] >= 1.0
        assert all(y > x for x, y in zip(b, b[1:]))

    def test_snapshot_and_reset(self):
        reg = metrics.Registry()
        c = reg.counter("n_total")
        h = reg.histogram("t_seconds", buckets=(1.0, 10.0))
        g = reg.gauge("live", fn=lambda: 7)
        c.inc(3); h.observe(0.5)
        snap = reg.snapshot()
        assert snap["n_total"] == 3
        assert snap["t_seconds_count"] == 1
        assert snap["live"] == 7
        reg.reset()
        snap = reg.snapshot()
        assert snap["n_total"] == 0 and snap["t_seconds_count"] == 0
        assert snap["live"] == 7                 # callback gauges read live

    def test_prometheus_exposition_lints_clean(self):
        import os
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "tools"))
        from check_prom import lint
        reg = metrics.Registry()
        reg.counter("req_total", "requests").inc(5)
        reg.gauge("depth", "queue depth").set(2)
        h = reg.histogram("lat_seconds", buckets=(0.1, 1.0), help="latency")
        h.observe(0.05); h.observe(3.0)
        text = reg.prometheus()
        assert lint(text) == []
        assert 'lat_seconds_bucket{le="+Inf"} 2' in text
        assert "# TYPE req_total counter" in text


# ---------------------------------------------------------------------------
# Engine wiring: schemas, guards, reset, queue observability, trace spans
# ---------------------------------------------------------------------------

# frozen compatibility schema of engine.metrics() — BENCH_serve.json rows
# read these keys; extend deliberately, never let them drift silently
METRICS_KEYS = {
    "requests", "requests_per_sec", "new_tokens", "tokens_per_sec",
    "mean_ttft_s", "max_ttft_s", "preemptions",
    "decode_compiles", "decode_shapes", "decode_steps", "decode_tok_per_s",
    "prefill_compiles", "prefill_shapes", "prefill_batches",
    "prefill_tok_per_s", "prefill_kernel",
    "prefix_hit_rate", "prefix_hit_tokens", "cached_blocks",
    "cow_copies", "prefix_evictions", "queue_depth",
    "warmup_seconds", "post_warmup_compiles", "slo_goodput",
}

# frozen registry series names (snapshot() expands histograms with these
# suffixes: _count/_sum/_mean/_p50/_p99/_max)
REGISTRY_NAMES = {
    "serve_decode_steps_total", "serve_decode_tokens_total",
    "serve_decode_seconds_total", "serve_prefill_batches_total",
    "serve_prefill_tokens_total", "serve_prefill_seconds_total",
    "serve_prompt_tokens_total", "serve_prefix_hit_tokens_total",
    "serve_requests_finished_total", "serve_new_tokens_total",
    "serve_ttft_seconds", "serve_decode_step_seconds",
    "serve_tpot_seconds", "serve_request_e2e_seconds",
    "serve_slo_goodput",
    "serve_running_requests", "serve_decode_compiles",
    "serve_prefill_compiles",
    "serve_warmup_seconds", "serve_post_warmup_compiles",
    "serve_queue_depth", "serve_queue_wait_seconds",
    "serve_requests_admitted_total", "serve_preemptions_total",
    "pool_cow_copies_total", "pool_prefix_evictions_total",
    "pool_free_blocks", "pool_cached_blocks",
}


class TestEngineWiring:
    def test_metrics_golden_keys(self, smollm):
        cfg, model, params = smollm
        eng = _engine(model, params)
        assert set(eng.metrics()) == METRICS_KEYS       # empty engine
        eng.submit(_prompt(cfg, 6), 3)
        while eng.has_work():
            eng.step()
        assert set(eng.metrics()) == METRICS_KEYS       # after serving

    def test_registry_golden_names(self, smollm):
        _, model, params = smollm
        eng = _engine(model, params)
        assert set(eng.registry.names()) == REGISTRY_NAMES
        hist_names = {n for n in REGISTRY_NAMES
                      if isinstance(eng.registry.get(n), metrics.Histogram)}
        snap = eng.registry.snapshot()
        expect = (REGISTRY_NAMES - hist_names) | {
            f"{n}{suf}" for n in hist_names
            for suf in ("_count", "_sum", "_mean", "_p50", "_p99", "_max")}
        assert set(snap) == expect

    def test_zero_elapsed_rates_are_zero(self, smollm):
        """A single-step trace compiles on every step, so the steady-state
        timers never accumulate — rates must report 0.0, not inf."""
        cfg, model, params = smollm
        eng = _engine(model, params)
        eng.submit(_prompt(cfg, 6), 2)
        eng.step()                                # prefill + 1st decode: all
        m = eng.metrics()                         # signatures fresh
        assert m["decode_tok_per_s"] == 0.0
        assert m["prefill_tok_per_s"] == 0.0
        assert np.isfinite(m["decode_tok_per_s"])
        # prometheus exposition must stay float-clean too
        assert "inf" not in eng.registry.prometheus()

    def test_metrics_strict_json_on_zero_finished_runs(self, smollm):
        """Regression: metrics() used to emit float('nan') for mean/max
        TTFT before anything finished, which json.dumps turns into
        non-strict NaN literals that the /snapshot endpoint (and any strict
        parser) rejects. Undefined TTFT is None now, in every branch."""
        cfg, model, params = smollm
        eng = _engine(model, params)
        m = eng.metrics()                         # nothing submitted
        assert m["mean_ttft_s"] is None and m["max_ttft_s"] is None
        assert m["tokens_per_sec"] == 0.0
        json.loads(json.dumps(m, allow_nan=False))
        eng.submit(_prompt(cfg, 6), 3)
        eng.step()                                # in flight, none finished
        if not eng.finished:
            assert eng.metrics()["mean_ttft_s"] is None
        while eng.has_work():
            eng.step()
        m = eng.metrics()
        assert m["mean_ttft_s"] is not None and m["mean_ttft_s"] >= 0.0
        json.loads(json.dumps(m, allow_nan=False))

    def test_slo_accounting(self, smollm):
        """TPOT/e2e histograms fill at _finish and the goodput gauge grades
        finished requests against the configured SLOs: impossible SLOs give
        0.0, generous ones 1.0, none (or nothing finished) reads 1.0."""
        cfg, model, params = smollm
        eng = _engine(model, params, slo_ttft_s=1e-9, slo_tpot_s=1e-9)
        assert eng.metrics()["slo_goodput"] == 1.0     # vacuous: none done
        for i in range(2):
            eng.submit(_prompt(cfg, 6, seed=i), 4)
        while eng.has_work():
            eng.step()
        m = eng.metrics()
        assert m["slo_goodput"] == 0.0                 # nothing beats 1ns
        assert eng.registry.get("serve_slo_goodput").value == 0.0
        assert eng.registry.get("serve_tpot_seconds").count == 2
        assert eng.registry.get("serve_request_e2e_seconds").count == 2
        assert eng.registry.get("serve_tpot_seconds").max > 0.0
        # generous SLOs: everything meets them
        eng2 = _engine(model, params, slo_ttft_s=3600.0, slo_tpot_s=3600.0)
        eng2.submit(_prompt(cfg, 6), 4)
        while eng2.has_work():
            eng2.step()
        assert eng2.metrics()["slo_goodput"] == 1.0
        # reset drops the finished list, so the gauge reads vacuous again
        eng.reset_metrics()
        assert eng.registry.get("serve_slo_goodput").value == 1.0
        assert eng.registry.get("serve_tpot_seconds").count == 0

    def test_reset_metrics_resets_request_level_stats(self, smollm):
        cfg, model, params = smollm
        eng = _engine(model, params)
        for i in range(3):
            eng.submit(_prompt(cfg, 6, seed=i), 4)
        while eng.has_work():
            eng.step()
        assert eng.metrics()["requests"] == 3
        assert eng.registry.get("serve_ttft_seconds").count == 3
        eng.reset_metrics()
        m = eng.metrics()
        assert m["requests"] == 0
        assert m["preemptions"] == 0
        assert m["mean_ttft_s"] is None           # TTFT samples gone
        snap = eng.registry.snapshot()
        assert snap["serve_ttft_seconds_count"] == 0
        assert snap["serve_queue_wait_seconds_count"] == 0
        assert snap["serve_requests_finished_total"] == 0
        # shape caches stay warm: reset is for steady-state benching
        assert eng.metrics()["decode_shapes"] > 0

    def test_queue_observability_under_pool_pressure(self, smollm):
        """A pool too small for the full load: requests queue (depth gauge,
        wait histogram) and the running set preempts (counter)."""
        cfg, model, params = smollm
        eng = _engine(model, params, block_size=2, num_blocks=9,
                      max_running=3)
        for i in range(4):
            eng.submit(_prompt(cfg, 4, seed=i), 6)
        # before any step everything waits: the live gauge reads the queue
        assert eng.registry.get("serve_queue_depth").value == 4
        assert eng.metrics()["queue_depth"] == 4
        depth_seen = []
        while eng.has_work():
            eng.step()
            depth_seen.append(eng.registry.get("serve_queue_depth").value)
        m = eng.metrics()
        assert m["requests"] == 4
        assert m["preemptions"] >= 1
        assert eng.registry.get("serve_preemptions_total").value >= 1
        # every admission (including re-admissions) observed a queue wait
        qw = eng.registry.get("serve_queue_wait_seconds")
        assert qw.count == \
            eng.registry.get("serve_requests_admitted_total").value
        assert qw.count >= 4 + m["preemptions"]
        assert qw.max > 0.0
        assert depth_seen[-1] == 0                # drained

    def test_trace_validity_over_served_load(self, smollm, tmp_path):
        """Serve a real mixed load with tracing on: the JSON parses, the
        expected span taxonomy is present, compile instants fire, and spans
        nest stack-like per thread."""
        cfg, model, params = smollm
        trace.enable()
        eng = _engine(model, params)
        for i in range(3):
            eng.submit(_prompt(cfg, 5 + 3 * i, seed=i), 4)
        while eng.has_work():
            eng.step()
        path = tmp_path / "serve_trace.json"
        n = trace.save(str(path))
        assert n > 0
        doc = json.loads(path.read_text())
        assert doc["displayTimeUnit"] == "ms"
        evs = doc["traceEvents"]
        names = {e["name"] for e in evs}
        assert {"serve.admit", "serve.prefill_batch",
                "serve.decode_step"} <= names
        assert "serve.decode_compile" in names    # instant events
        assert "serve.queue_wait" in names        # async intervals
        for e in evs:
            assert e["ph"] in ("X", "i", "M", "b", "e")
            if e["ph"] == "X":
                assert e["dur"] >= 0 and "tid" in e
        assert _nesting_ok(evs)

    def test_tracing_off_leaves_no_events(self, smollm):
        cfg, model, params = smollm
        eng = _engine(model, params)
        eng.submit(_prompt(cfg, 6), 2)
        while eng.has_work():
            eng.step()
        assert not trace.enabled()
        assert trace.save("/tmp/unused.json") == 0


STEP_LEAVES = {"serve.admit", "serve.prepare", "serve.prefill_batch",
               "serve.decode_step", "serve.sample", "serve.emit"}


def _covered(intervals):
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > end:
            total += e - max(s, end)
            end = e
    return total


class TestEngineSpans:
    """The engine's spans divide each ``step()`` into its parts, with the
    counts of what it dispatched, and its jitted programs carry names."""

    def _serve(self, eng, cfg, n=3):
        for i in range(n):
            eng.submit(_prompt(cfg, 5 + 3 * i, seed=i), 6)
        walls = []
        while eng.has_work():
            t0 = time.perf_counter()
            eng.step()
            walls.append((t0, time.perf_counter()))
        return walls

    def test_leaf_spans_cover_each_step(self, smollm):
        cfg, model, params = smollm
        eng = _engine(model, params)
        trace.enable()
        walls = self._serve(eng, cfg)
        assert len(walls) >= 5
        for lo, hi in walls:
            spans = trace.spans(lo, hi)
            parents = {s.parent for s in spans}
            leaves = [(max(s.start, lo), min(s.end, hi)) for s in spans
                      if s.name in STEP_LEAVES and s.id not in parents]
            assert {"serve.decode_step", "serve.sample",
                    "serve.emit"} <= {s.name for s in spans}
            assert _covered(leaves) >= 0.9 * (hi - lo)

    def test_span_counts_match_what_was_dispatched(self, smollm):
        cfg, model, params = smollm
        eng = _engine(model, params, max_running=3)
        calls = {"decode": [], "prefill": []}

        def spy(kind, fn):
            def call(p, tok, *rest):
                lens = rest[2] if kind == "prefill" else None
                calls[kind].append((tok.shape, lens))
                return fn(p, tok, *rest)
            return call

        for attr in ("_decode", "_decode_paged"):
            setattr(eng, attr, spy("decode", getattr(eng, attr)))
        for attr in ("_prefill_chunk", "_prefill_chunk_paged"):
            if getattr(eng, attr) is not None:
                setattr(eng, attr, spy("prefill", getattr(eng, attr)))
        trace.enable()
        self._serve(eng, cfg, n=3)
        spans = trace.spans(float("-inf"), float("inf"))
        dec = [s.args for s in spans if s.name == "serve.decode_step"]
        pre = [s.args for s in spans if s.name == "serve.prefill_batch"]
        assert len(dec) == len(calls["decode"]) > 0
        assert len(pre) == len(calls["prefill"]) > 0
        for a, (shape, _) in zip(dec, calls["decode"]):
            assert a["padded_rows"] == shape[0]
            assert 0 < a["rows"] <= a["padded_rows"]
        for a, (shape, lens) in zip(pre, calls["prefill"]):
            assert a["padded_rows"] == shape[0]
            assert a["padded_tokens"] == shape[0] * shape[1]
            real = np.asarray(lens)[:a["rows"]]
            assert a["tokens"] == int(real.sum())
        # every request prefills once and decodes a row per later token
        assert sum(a["rows"] for a in pre) == 3
        assert sum(a["rows"] for a in dec) == sum(
            len(r.out_tokens) - 1 for r in eng.finished)
        assert sum(s.args["finished"] for s in spans
                   if s.name == "serve.emit") == 3

    def test_one_queue_wait_per_admission(self, smollm):
        cfg, model, params = smollm
        eng = _engine(model, params, block_size=2, num_blocks=9,
                      max_running=3)
        trace.enable()
        for i in range(4):
            eng.submit(_prompt(cfg, 4, seed=i), 6)
        while eng.has_work():
            eng.step()
        waits = [s for s in trace.spans(float("-inf"), float("inf"))
                 if s.name == "serve.queue_wait"]
        hist = eng.registry.get("serve_queue_wait_seconds")
        assert eng.metrics()["preemptions"] >= 1      # re-admissions too
        assert len(waits) == hist.count == \
            eng.registry.get("serve_requests_admitted_total").value
        assert sum(s.end - s.start for s in waits) == pytest.approx(
            hist.sum, rel=1e-9, abs=1e-12)
        assert {s.args["req_id"] for s in waits} == {0, 1, 2, 3}

    def test_jitted_programs_carry_names(self, smollm, caplog):
        cfg, model, params = smollm
        eng = _engine(model, params)
        names = {"_prefill": "serve_prefill", "_decode": "serve_decode",
                 "_decode_paged": "serve_decode_paged",
                 "_prefill_chunk": "serve_prefill_chunk",
                 "_prefill_chunk_paged": "serve_prefill_paged",
                 "_sample": "serve_sample"}
        for attr, name in names.items():
            fn = getattr(eng, attr)
            assert fn is None or fn.__name__ == name, attr
        # the modules that compile while serving carry those names
        jax.config.update("jax_log_compiles", True)
        try:
            with caplog.at_level("WARNING", logger="jax"):
                self._serve(eng, cfg, n=2)
        finally:
            jax.config.update("jax_log_compiles", False)
        compiled = " ".join(r.getMessage() for r in caplog.records)
        for name in ("serve_sample", "serve_decode_paged" if
                     eng.paged_kernel else "serve_decode"):
            assert f"jit({name})" in compiled, name
        from repro.core.tsqr import RStreamer
        rs = RStreamer(4)
        x = jnp.ones((8, 4), jnp.float32)
        assert "@jit_calib_fold_first" in rs._first.lower(x).as_text()
        assert "@jit_calib_fold " in rs._update.lower(
            jnp.eye(4), x).as_text()

    def test_calibration_spans_nest(self, smollm):
        from repro.core.calibrate import Calibrator
        cfg, model, params = smollm
        cal = Calibrator(max_tokens_per_record=8)
        trace.enable()
        tokens = jnp.asarray(np.stack([_prompt(cfg, 12, seed=s)
                                       for s in range(2)]))
        model.capture_forward(params, {"tokens": tokens}, cal)
        spans = trace.spans(float("-inf"), float("inf"))
        by_id = {s.id: s for s in spans}
        caps = [s for s in spans if s.name == "calib.capture"]
        recs = [s for s in spans if s.name == "calib.record"]
        folds = [s for s in spans if s.name == "calib.fold"]
        assert len(caps) == 1 and len(recs) == len(cal.streams)
        assert all(by_id[r.parent].name == "calib.capture" for r in recs)
        assert all(by_id[f.parent].name == "calib.record" for f in folds)
        # 24 rows a record, folded 8 at a time; a record of the input the
        # record before it folded (wk, wv, up) takes that fold's R
        folded = {r.id for r in recs if not r.args["shared"]}
        assert len(folded) == 4 * cfg.n_layers
        assert len(folds) == 3 * len(folded)
        assert all(f.parent in folded for f in folds)
        assert {(f.args["rows"], f.args["n"]) for f in folds} >= {(8, cfg.d_model)}


# ---------------------------------------------------------------------------
# Numerics monitors
# ---------------------------------------------------------------------------

class TestNumerics:
    def test_flags_ill_conditioned_silent_on_well_conditioned(self):
        rs = {"bad": _ill_conditioned_r(cond=1e9),
              "good": _ill_conditioned_r(cond=1e3, seed=1),
              "warn": _ill_conditioned_r(cond=3e6, seed=2)}
        tokens = {p: 64 for p in rs}
        by = {h.path: h for h in numerics.check_r_factors(rs, tokens)}
        assert by["bad"].level == "fail"
        assert 1e8 < by["bad"].cond < 1e11     # cond1 within ~n of cond2
        assert by["good"].level == "ok" and not by["good"].reasons
        assert by["warn"].level == "warn"
        assert numerics.worst_level(list(by.values())) == "fail"

    def test_insufficient_data_flagged(self):
        r = _ill_conditioned_r(n=16, k=64, cond=1e2)
        by = {h.path: h
              for h in numerics.check_r_factors({"x": r}, {"x": 8})}
        assert by["x"].level in ("warn", "fail")
        assert any("insufficient data" in r for r in by["x"].reasons)

    def test_singular_r_is_inf_and_fails(self):
        r = np.triu(np.ones((8, 8), np.float32))
        r[3, 3] = 0.0                             # rank-deficient
        assert numerics.triangular_cond(r) == float("inf")
        h = numerics.check_r_factors({"x": r})[0]
        assert h.level == "fail"

    def test_triangular_cond_matches_dense_estimate(self):
        r = _ill_conditioned_r(n=12, k=48, cond=1e4, seed=3)
        est = numerics.triangular_cond(r)
        ref = np.linalg.cond(r, p=1)
        assert est == pytest.approx(ref, rel=1e-3)

    def test_calibrator_duck_type(self, smollm):
        cfg, model, params = smollm
        from repro.core.calibrate import calibrate_model
        from repro.data import DataConfig, TokenPipeline
        pipe = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size,
                                        seq_len=32, global_batch=4), cfg)
        cal = calibrate_model(model, params, [pipe.get_batch(0)])
        healths = numerics.check_calibration(cal)
        assert healths and all(h.tokens is not None for h in healths)
        report = numerics.format_report(healths)
        assert "layers checked" in report

    def test_residual_vs_bound_grading(self):
        class Rep:
            def __init__(self, path, res, bound):
                self.path = path
                self.rel_err_weighted = res
                self.rel_err_bound = bound
        reports = [Rep("tight", 0.105, 0.10),     # 1.05x: ok
                   Rep("loose", 0.5, 0.10),       # 5x: warn
                   Rep("broken", 2.0, 0.10),      # 20x: fail
                   Rep("no_rf", float("nan"), float("nan"))]
        by = {h.path: h for h in numerics.check_compression(reports)}
        assert set(by) == {"tight", "loose", "broken"}   # nan skipped
        assert by["tight"].level == "ok"
        assert by["loose"].level == "warn"
        assert by["broken"].level == "fail"

    def test_compress_reports_carry_bound(self, smollm):
        """compress_params emits rel_err_bound <= rel_err_weighted (the
        bound is the attainable optimum) and finite for calibrated layers."""
        cfg, model, params = smollm
        from repro.config import CompressConfig
        from repro.core.calibrate import calibrate_model
        from repro.core.compress import compress_model
        from repro.data import DataConfig, TokenPipeline
        pipe = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size,
                                        seq_len=32, global_batch=4), cfg)
        cal = calibrate_model(model, params, [pipe.get_batch(0)])
        _, reports = compress_model(
            model, params, cal,
            CompressConfig(method="coala", ratio=0.6, lam=4.0, mu=-1.0))
        assert reports
        for rep in reports:
            assert np.isfinite(rep.rel_err_bound)
            assert rep.rel_err_bound <= rep.rel_err_weighted * (1 + 1e-4)

    def test_sharded_calibration_monitor_parity(self):
        """The cond monitor must reach the same verdicts through the
        sharded butterfly-reduce path as single-device: ill-conditioned
        synthetic activations flagged, well-conditioned silent — on the
        cond=1e9 fixture from test_dist_calibrate."""
        out = run_with_devices("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.core.calibrate import Calibrator
            from repro.dist.calibrate import ShardedCalibration, \\
                combine_r_shards
            from repro.core.tsqr import square_r
            from repro.obs import numerics

            def x_with_cond(n, k, cond, seed):
                rng = np.random.RandomState(seed)
                u, _ = np.linalg.qr(rng.standard_normal((k, n)))
                v, _ = np.linalg.qr(rng.standard_normal((n, n)))
                s = np.logspace(0, -np.log10(cond), n)
                return (u @ np.diag(s) @ v.T).astype(np.float32)

            n, k, shards = 16, 512, 8
            mesh = jax.make_mesh((shards,), ("data",),
                                 devices=jax.devices()[:shards],
                                 axis_types=(jax.sharding.AxisType.Auto,))
            cases = {"bad": 1e9, "good": 1e3}
            factors, tokens = {}, {}
            single = Calibrator()
            for seed, (path, cond) in enumerate(cases.items()):
                x = x_with_cond(n, k, cond, seed=seed)
                single.record(path, jnp.asarray(x))
                per = k // shards
                locs = []
                for s_i in range(shards):
                    c = Calibrator()
                    c.record(path, jnp.asarray(x[s_i*per:(s_i+1)*per]))
                    locs.append(square_r(c.streams[path].r))
                factors[path] = combine_r_shards(jnp.stack(locs), mesh)
                tokens[path] = k
            sharded = ShardedCalibration(factors=factors, tokens=tokens,
                                         n_shards=shards)
            for name, cal in (("single", single), ("sharded", sharded)):
                by = {h.path: h for h in numerics.check_calibration(cal)}
                assert by["bad"].level == "fail", (name, by["bad"])
                assert by["good"].level == "ok", (name, by["good"])
                print(name, "bad=%.3e" % by["bad"].cond,
                      "good=%.3e" % by["good"].cond)
            print("MONITOR_PARITY_OK")
        """)
        assert "MONITOR_PARITY_OK" in out
