"""The reader of ``calib.shared_record_share.calib``: the share of the
program's ``calib.record`` spans that took the fold of the record before
them. Exact on a synthetic span list, nothing where no record span (or no
``shared`` arg) is there to read, and 3 of every 7 records in a traced run
of the calibration cell at the tiny size (wk, wv and up of each layer)."""
import time

import pytest

import chipbench_tiny
from benchmarks.chip import harness, loader
from repro.obs import trace as obs_trace

NAME = "calib.shared_record_share.calib"
CELL = "smollm_135m.calib_stream"


def _span(i, name, **args):
    return obs_trace.Span(name, 1.0 + i, 1.5 + i, i, None, args, 0)


def _read(monkeypatch, spans):
    monkeypatch.setattr(obs_trace, "spans", lambda lo, hi: spans)
    rec = {"host_window": (0.0, time.perf_counter())}
    return loader.metric_reader(NAME)(rec)


def test_declared_for_the_calibration_cell():
    per = loader.metrics_for(loader.benchmark(), CELL, "per_layer")
    assert NAME in [m["name"] for m in per]


def test_exact_share_of_a_synthetic_span_list(monkeypatch):
    spans = [_span(0, "calib.capture")]
    spans += [_span(1 + i, "calib.record", path=f"p{i}", shared=i % 4 == 1)
              for i in range(8)]
    spans += [_span(20, "calib.fold", rows=8, n=64)]
    assert _read(monkeypatch, spans) == pytest.approx(100.0 * 2 / 8)


@pytest.mark.parametrize("spans", [
    [],
    [_span(0, "calib.capture"), _span(1, "calib.fold", rows=8, n=64)],
    # a program whose records do not say whether they shared a fold
    [_span(0, "calib.record", path="a", tokens=8)],
], ids=["empty", "no_records", "no_shared_arg"])
def test_nothing_to_read_gives_none(monkeypatch, spans):
    assert _read(monkeypatch, spans) is None


def test_traced_run_reads_three_of_seven():
    r = harness.run_cell(CELL, 2 ** 33 + 29, 1.5, True, require_chip_=False,
                         overrides=chipbench_tiny.overrides(CELL))
    assert r["correct"]
    assert r["metrics"][NAME]["value"] == pytest.approx(100.0 * 3 / 7)
