"""The chip benchmark finds its parts by name, and BENCHMARK.json keeps the
shape its readers rely on."""
import json
import os
import re
import shutil
import sys

import pytest

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))]

from benchmarks.chip import loader  # noqa: E402

BENCH = loader.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_new_config_mix_and_metric_are_found_without_edits(tmp_path):
    """A later change adds files and entries; nothing existing is edited."""
    root = tmp_path / "chip"
    for sub in ("configs", "traffic", "metrics", "limits", "kernels"):
        shutil.copytree(os.path.join(loader.HERE, sub), root / sub)
    shutil.copy(os.path.join(loader.HERE, "peaks.json"), root)
    conf = loader.config("smollm_135m")
    conf["name"] = "new_model"
    (root / "configs" / "new_model.json").write_text(json.dumps(conf))
    mix = dict(loader.traffic("chat_poisson"), rate_per_s=9.5)
    (root / "traffic" / "new_mix.json").write_text(json.dumps(mix))
    (root / "limits" / "new_model.new_mix.json").write_text(json.dumps(
        {"served_logit_gap": {"limit": 1.0, "pass_if": "le"}}))
    (root / "metrics" / "queue.depth.new.py").write_text(
        "def read(rec):\n    return rec.get('depth')\n")
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "new_model.new_mix",
                               "config": "new_model", "traffic": "new_mix",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "queue.depth.new", "unit": "1",
                               "better": "lower", "source": "program_counter",
                               "layer": "serve/scheduler.py",
                               "moves": "ttft_p90_ms",
                               "workloads": ["new_model.new_mix"]})
    cell = loader.cell(bench, "new_model.new_mix")
    assert loader.config(cell["config"], str(root))["name"] == "new_model"
    assert loader.traffic(cell["traffic"], str(root))["rate_per_s"] == 9.5
    assert loader.limits(cell["name"], str(root))["served_logit_gap"]
    names = [m["name"] for m in
             loader.metrics_for(bench, "new_model.new_mix", "per_layer")]
    assert names == ["queue.depth.new"]
    assert loader.metric_reader("queue.depth.new", str(root))(
        {"depth": 3}) == 3
    assert loader.metric_reader("queue.depth.new", str(root))({}) is None
    ends = [m["name"] for m in
            loader.metrics_for(bench, "new_model.new_mix", "end_to_end")]
    assert ends == ["setup_s"]


ECHO_KIND = """
def run(*, conf, mix, seed, seconds, ann, tracer, compiles, control,
        t_start):
    tracer.start([])
    tracer.maybe_stop([], force=True)
    gap = mix["gap"] * (10 if control else 1)
    return {"e2e": {"setup_s": 0.5, "echo_ms": float(seed % 7)},
            "record": {"depth": 4}, "attempted": 3, "failed": 0,
            "device": {"platform": "cpu", "kind": "cpu", "count": 1,
                       "memory_peak_bytes": 0},
            "compared": {"echo_gap": gap}, "info": {}}
"""


def test_new_kind_of_traffic_runs_without_edits(tmp_path):
    """A kind of traffic is a module of its own: a copy of the
    benchmark with ``kinds/echo.py`` and its files dropped in runs a cell
    of that kind through the harness, and its control is not correct."""
    from benchmarks.chip import harness
    root = tmp_path / "chip"
    shutil.copytree(loader.HERE, root,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "kinds" / "echo.py").write_text(ECHO_KIND)
    (root / "traffic" / "echo_mix.json").write_text(json.dumps(
        {"kind": "echo", "gap": 0.01}))
    (root / "limits" / "smollm_135m.echo_mix.json").write_text(json.dumps(
        {"echo_gap": {"limit": 0.05, "pass_if": "le"}}))
    (root / "metrics" / "echo_depth.py").write_text(
        "def read(rec):\n    return rec.get('depth')\n")
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "smollm_135m.echo_mix",
                               "config": "smollm_135m",
                               "traffic": "echo_mix", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "echo_ms", "unit": "ms",
                                "better": "lower", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["smollm_135m.echo_mix"]})
    bench["per_layer"].append({"name": "echo_depth", "unit": "1",
                               "better": "lower", "source": "program_counter",
                               "layer": "echo", "moves": "echo_ms",
                               "workloads": ["smollm_135m.echo_mix"]})
    kw = dict(bench=bench, root=str(root), require_chip_=False)
    r = harness.run_cell("smollm_135m.echo_mix", 2 ** 33 + 3, 1.0, False,
                         **kw)
    assert r["correct"] and r["attempted"] == 3
    assert r["metrics"] == {"setup_s": {"value": 0.5, "unit": "s"},
                            "echo_ms": {"value": float((2 ** 33 + 3) % 7),
                                        "unit": "ms"}}
    assert r["compared"] == {"echo_gap": {"value": 0.01, "limit": 0.05,
                                          "pass_if": "le"}}
    assert not harness.run_cell("smollm_135m.echo_mix", 5, 1.0, False,
                                control=True, **kw)["correct"]
    with pytest.raises(FileNotFoundError):
        loader.kind("no_such_kind", str(root))


def test_one_reader_serves_a_measure_in_every_cell(tmp_path):
    """``device_idle.chat`` and ``device_idle.calib`` share
    ``metrics/device_idle.py``; a reader of the full name comes first."""
    idle = loader.metric_reader("device_idle.chat")
    assert idle is not None
    assert loader.metric_reader("device_idle.calib").__module__ == \
        idle.__module__
    root = tmp_path / "chip"
    (root / "metrics").mkdir(parents=True)
    (root / "metrics" / "q.py").write_text("def read(rec):\n    return 1\n")
    (root / "metrics" / "q.b.py").write_text("def read(rec):\n    return 2\n")
    assert loader.metric_reader("q.a", str(root))({}) == 1
    assert loader.metric_reader("q.b.c", str(root))({}) == 2
    with pytest.raises(FileNotFoundError):
        loader.metric_reader("nothing.here", str(root))


def test_every_cell_has_its_files():
    for w in BENCH["workloads"]:
        conf = loader.config(w["config"])
        assert conf["name"] == w["config"]
        mix = loader.traffic(w["traffic"])
        assert callable(loader.kind(mix["kind"]).run)
        lim = loader.limits(w["name"])
        assert all(v["pass_if"] in ("le", "ge") for v in lim.values())
        per = loader.metrics_for(BENCH, w["name"], "per_layer")
        ends = loader.metrics_for(BENCH, w["name"], "end_to_end")
        assert per and len(ends) >= 2
        assert "setup_s" in [m["name"] for m in ends]
        for m in per:
            assert callable(loader.metric_reader(m["name"]))
            assert m["moves"] in [e["name"] for e in ends]


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    assert 1 <= BENCH["run_seconds"] <= 51
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.exists(os.path.join(loader.CHECKOUT, c["file"]))
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        names.append(c["name"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("device_trace", "host_clock")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all("\n" not in x and len(x) <= 200 for x in layers)
    assert len({m["name"] for m in metrics}) == len(metrics)
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in metrics:
        assert set(m.get("workloads", cells)) <= cells
    with pytest.raises(KeyError):
        loader.cell(BENCH, "no_such.cell")
