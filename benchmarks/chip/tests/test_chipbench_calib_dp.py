"""The data-parallel calibration cell (``kinds/calib_dp.py``) at a CPU size
over four host devices: a sound run passes and compiles nothing in its
window, its control fails, and the collective-permute share reads nothing
without such operations and their exact share on a synthetic trace.

The cell is not in ``BENCHMARK.json`` until chip runs set its limits: the
tests run it from the entries it will take there (``BENCH``) and from a
copy of the benchmark directory that holds this size's limits."""
import copy
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

import chipbench_tiny
from benchmarks.chip import loader

CELL = "smollm_135m.calib_dp4"
# at this size a sound run reads 6.0e-7 to 6.1e-7 and the control 1.4e-5 to
# 1.8e-5
LIMIT = {"fold_gram_gap": {"limit": 3e-6, "pass_if": "le"}}


def _bench():
    """``BENCHMARK.json`` with the cell's entries added."""
    bench = copy.deepcopy(loader.benchmark())
    bench["workloads"].append({
        "name": CELL, "config": "smollm_135m", "traffic": "calib_dp4",
        "chips": 4, "why": "data-parallel COALA calibration over data=4"})
    for m in bench["end_to_end"]:
        if m["name"] == "calib_tok_s":
            m["workloads"].append(CELL)
    bench["per_layer"].append({
        "name": "calib.collective_share.calib_dp4", "unit": "%",
        "better": "lower", "source": "device_trace",
        "layer": "dist/calibrate.py", "moves": "calib_tok_s",
        "workloads": [CELL]})
    return bench


BENCH = _bench()


def _runs(root):
    """Sound and control runs, in a process of four host devices."""
    code = textwrap.dedent(f"""
        import json, sys
        sys.path[:0] = [{chipbench_tiny.CHECKOUT!r},
                        {os.path.join(chipbench_tiny.CHECKOUT, "src")!r},
                        {os.path.dirname(os.path.abspath(__file__))!r}]
        import chipbench_tiny
        from benchmarks.chip import harness
        over = {{"config": {{"model": chipbench_tiny.TINY,
                            "init": chipbench_tiny.INIT}},
                "traffic": {{"batch": 4, "seq_len": 32, "steps": 2,
                            "ref_rows": 2}}}}
        bench = json.loads({json.dumps(json.dumps(BENCH))})
        out = [harness.run_cell({CELL!r}, 2 ** 33 + 41, 1.0, False,
                                bench=bench, root={root!r},
                                require_chip_=False, overrides=over,
                                control=c) for c in (False, True)]
        print(json.dumps(out))
    """)
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("chip") / "chip")
    shutil.copytree(loader.HERE, root, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    with open(os.path.join(root, "limits", f"{CELL}.json"), "w") as f:
        json.dump(LIMIT, f)
    return _runs(root)


def test_sound_run_is_correct_and_compiles_nothing_in_its_window(runs):
    r = runs[0]
    assert r["correct"], r["compared"]
    assert r["attempted"] > 0 and r["info"]["window_compiles"] == 0
    assert set(r["metrics"]) == {"setup_s", "calib_tok_s"}


def test_control_fails(runs):
    c = runs[1]
    assert not c["correct"], c["compared"]
    got = c["info"]["program_fold_gram_gap"]
    assert got <= LIMIT["fold_gram_gap"]["limit"]
    assert c["compared"]["fold_gram_gap"]["value"] > 3 * got


class _Trace:
    window_s = 4.0
    op_seconds = {"collective-permute-start": 0.25,
                  "collective-permute-done": 0.75, "fusion": 2.0}


def test_collective_share_exact_and_none_without_permutes():
    read = loader.metric_reader("calib.collective_share.calib_dp4")
    assert read({"trace": _Trace()}) == pytest.approx(25.0)
    assert read({}) is None
    none = _Trace()
    none.op_seconds = {"fusion": 2.0}
    assert read({"trace": none}) is None


def test_declared_for_the_cell():
    per = loader.metrics_for(BENCH, CELL, "per_layer")
    assert [m["name"] for m in per] == ["calib.collective_share.calib_dp4"]
    assert loader.cell(BENCH, CELL)["chips"] == 4
    e2e = loader.metrics_for(BENCH, CELL, "end_to_end")
    assert [m["name"] for m in e2e] == ["calib_tok_s", "setup_s"]
