"""The readers of the program's own spans (``repro.obs.trace.spans``): in a
traced run of its cell on the CPU at the tiny size each reads a finite
value in its range, and a program that records no spans, or whose window
cannot be read whole, gives nothing to read."""
import math
import time

import pytest

import chipbench_tiny
from benchmarks.chip import harness, loader
from repro.obs import trace as obs_trace

SEED = 2 ** 33 + 17
CHAT, CALIB = "smollm_135m.chat_poisson", "smollm_135m.calib_stream"
# metric -> (cell, least, most)
RANGES = {
    "engine.queue_wait_p90.chat": (CHAT, 0.0, 1500.0),
    "engine.host_share.chat": (CHAT, 0.0, 100.0),
    "engine.decode_row_use.chat": (CHAT, 1e-9, 100.0),
    "engine.prefill_token_use.chat": (CHAT, 1e-9, 100.0),
    "calib.dispatch_share.calib": (CALIB, 1e-9, 100.0),
}


def _run(cell, trace=True):
    return harness.run_cell(cell, SEED, 1.5, trace, require_chip_=False,
                            overrides=chipbench_tiny.overrides(cell))


@pytest.fixture(scope="module")
def traced():
    return {cell: _run(cell) for cell in (CHAT, CALIB)}


def test_every_new_metric_is_declared_for_its_cell():
    bench = loader.benchmark()
    for name, (cell, _, _) in RANGES.items():
        assert name in [m["name"] for m in
                        loader.metrics_for(bench, cell, "per_layer")]


@pytest.mark.parametrize("name", sorted(RANGES))
def test_traced_run_reads_the_metric_in_range(traced, name):
    cell, least, most = RANGES[name]
    r = traced[cell]
    assert r["correct"]
    v = r["metrics"][name]["value"]
    assert math.isfinite(v) and least <= v <= most, (name, v)


@pytest.mark.parametrize("name", sorted(RANGES))
def test_program_without_spans_gives_none(monkeypatch, name):
    rec = {"host_window": (0.0, time.perf_counter())}
    read = loader.metric_reader(name)
    monkeypatch.delattr(obs_trace, "spans")
    assert read(rec) is None


@pytest.mark.parametrize("name", sorted(RANGES))
def test_unreadable_window_gives_none(monkeypatch, name):
    rec = {"host_window": (0.0, time.perf_counter())}
    monkeypatch.setattr(obs_trace, "spans", lambda lo, hi: None)
    assert loader.metric_reader(name)(rec) is None
    monkeypatch.setattr(obs_trace, "spans", lambda lo, hi: [])
    assert loader.metric_reader(name)(rec) is None


def test_traced_run_of_a_program_without_spans_leaves_them_out(monkeypatch):
    """What the benchmark reads of a program that predates ``spans``: the
    run completes, and the line holds the older metrics only."""
    monkeypatch.delattr(obs_trace, "spans")
    r = _run(CHAT)
    assert r["correct"]
    assert not set(RANGES) & set(r["metrics"])
    assert "itl_p50.chat" in r["metrics"]
