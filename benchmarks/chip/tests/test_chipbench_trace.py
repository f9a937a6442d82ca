"""Trace reduction: busy union, idle gaps attributed to harness spans, and a
small trace recorded on a TPU v5e (four decode steps of a two-layer model
under ``engine.step`` / ``harness.bookkeeping`` annotations)."""
import os

import pytest

import chipbench_tiny  # noqa: F401  (puts the checkout on sys.path)
from benchmarks.chip import trace

SAMPLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                      "v5e_decode_steps.xplane.pb")


def test_union_clip_and_gaps():
    merged = trace.union([(5, 9), (0, 2), (1, 3), (8, 12)])
    assert merged == [(0, 3), (5, 12)]
    assert trace.clip(merged, 2, 10) == [(2, 3), (5, 10)]
    assert trace.gaps(trace.clip(merged, 2, 14), 2, 14) == [(3, 5), (12, 14)]
    assert trace.gaps([], 0, 4) == [(0, 4)]


def test_gap_label_is_the_span_covering_most_of_it():
    spans = [("engine.step", 0, 100), ("harness.bookkeeping", 100, 130),
             ("harness.submit", 130, 200)]
    assert trace.label((90, 125), spans) == "harness.bookkeeping"
    assert trace.label((140, 150), spans) == "harness.submit"
    assert trace.label((300, 310), spans) == "none"
    # nested spans: the innermost of equal covers
    assert trace.label((10, 20), [("calib.batch", 0, 100),
                                  ("engine.step", 5, 50)]) == "engine.step"


def test_stable_names():
    assert trace.stable_name(
        "%paged_attention.11 = bf16[32,3,3,64]{3,2,1,0} custom-call(...)"
    ) == "paged_attention"
    assert trace.stable_name("%fusion.133 = f32[2] fusion(x)") == "fusion"
    assert trace.stable_name("%copy-start.3 = (f32[1]) copy-start(y)") == \
        "copy-start"
    assert trace.stable_name("while.13") == "while"


def test_summary_breakdown_orders_and_caps():
    s = trace.Summary(window_s=1.0, busy_s=0.25,
                      op_seconds={f"op{i}": i * 0.01 for i in range(15)},
                      idle=[("harness.submit", 0.5),
                                          ("engine.step", 0.1),
                                          ("harness.submit", 0.15)],
                      chips=1)
    assert s.idle_share == pytest.approx(0.75)
    b = s.breakdown()
    assert len(b["device_ops"]) == 10 and b["device_ops"][0][0] == "op14"
    assert b["idle_gaps"][0] == ["harness.submit", pytest.approx(0.65)]


def test_recorded_v5e_trace():
    s = trace.reduce(SAMPLE, chips=1)
    assert s.chips == 1
    assert 0 < s.busy_s < s.window_s < 1.0
    # busy is a union: never more than the summed operation times
    assert s.busy_s <= sum(s.op_seconds.values()) + 1e-9
    assert "paged_attention" in s.op_seconds
    labels = {name for name, _ in s.idle}
    assert labels <= set(trace.HARNESS_SPANS) | {"none"}
    # the recording slept 4 ms under harness.bookkeeping after each step
    book = sum(sec for name, sec in s.idle if name == "harness.bookkeeping")
    assert book >= 4 * 0.004 * 0.9
    assert sum(sec for _, sec in s.idle) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
