"""Perf-regression gate (tools/check_bench.py): the CI step must go red.

Drives the gate the way CI does — artifact JSON vs a committed baseline —
and proves each failure class actually fails: a seeded throughput
regression outside the band, a violated hard invariant (which a baseline
refresh must NOT be able to relax), rows dropped from or added to the
schema, NaN/null values, and a benchmarks.run suite-error map. Plus the
green path: a fresh artifact validated against its own ``--update``
baseline passes, and small in-band drift passes.
"""
import copy
import json
import os
import pathlib
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "tools"))
import check_bench  # noqa: E402


def _artifact():
    """Minimal but schema-realistic benchmarks.run serve+dist artifact."""
    rows = [
        ("serve/paged_tok_per_s", "120.50"),
        ("serve/gather_decode_tok_per_s", "80.00"),
        ("serve/paged_vs_gather_decode_speedup", "1.450"),
        ("serve/warm_ttft_ms", "35.1"),
        ("serve/cold_ttft_ms", "2400.0"),
        ("serve/warmup_seconds", "12.31"),
        ("serve/post_warmup_compiles", 0),
        ("serve/offline_tok_per_s", "95.30"),
        ("serve/obs_overhead_pct", "1.25"),
        ("serve/slo_goodput", "1.0"),
        ("serve/serve_tpot_seconds_p50", "0.012"),
        ("serve/serve_tpot_seconds_p99", "0.019"),
        ("serve/serve_request_e2e_seconds_p50", "0.23"),
        ("serve/serve_request_e2e_seconds_p99", "0.41"),
        ("serve/spec_accept_rate", "0.912"),
        ("serve/spec_decode_speedup", "1.140"),
        ("serve/spec_greedy_parity", "1.0"),
        ("serve/spec_post_warmup_compiles", 0),
        ("serve/recalib_greedy_parity", "1.0"),
        ("serve/recalib_swaps", 1),
        ("serve/recalib_post_warmup_compiles", 0),
        ("serve/recalib_swap_ms", "45.2"),
        ("serve/recalib_tokens_to_clearance", 81),
        ("serve/recalib_r_gram_rel_err", "5.4e-07"),
        ("dist/calib_sharded_tok_per_s", "5400.0"),
        ("dist/r_gram_rel_err", "3.1e-07"),
    ]
    return {"benchmarks": ["serve", "dist"], "smoke": True, "errors": {},
            "rows": [{"name": n, "value": v, "notes": ""} for n, v in rows]}


@pytest.fixture()
def gate(tmp_path):
    """(artifact dict, writer, checker) against a tmp baseline dir."""
    art_path = tmp_path / "BENCH_serve.json"
    base_path = tmp_path / "baselines" / "BENCH_serve.json"

    def write(artifact):
        art_path.write_text(json.dumps(artifact))
        return art_path

    def check(artifact):
        return check_bench.check_artifact(write(artifact), base_path)

    write(_artifact())
    assert check_bench.update_baseline(art_path, base_path) == []
    return _artifact(), check, base_path


def test_fresh_artifact_passes_its_baseline(gate):
    art, check, _ = gate
    assert check(art) == []


def test_in_band_drift_passes(gate):
    art, check, _ = gate
    art["rows"][0]["value"] = "100.00"          # -17% of 120.5: inside ±40%
    assert check(art) == []


def test_seeded_throughput_regression_fails(gate):
    art, check, _ = gate
    art["rows"][0]["value"] = "60.00"           # -50%: outside the band
    errs = check(art)
    assert any("serve/paged_tok_per_s" in e and "outside" in e for e in errs)


def test_band_override_tightens(gate):
    art, check, base_path = gate
    doc = json.loads(base_path.read_text())
    doc["rows"]["serve/paged_tok_per_s"]["band_pct"] = 5
    base_path.write_text(json.dumps(doc))
    art["rows"][0]["value"] = "100.00"          # -17%: fine at 40, not at 5
    errs = check(art)
    assert any("serve/paged_tok_per_s" in e for e in errs)


@pytest.mark.parametrize("name,value,frag", [
    ("serve/post_warmup_compiles", 3, "hard invariant"),
    ("serve/obs_overhead_pct", "7.5", "hard invariant"),
    ("serve/slo_goodput", "0.75", "hard invariant"),
    ("serve/paged_vs_gather_decode_speedup", "0.90", "hard invariant"),
    ("serve/spec_decode_speedup", "0.95", "hard invariant"),
    ("serve/spec_greedy_parity", "0.0", "hard invariant"),
    ("serve/spec_accept_rate", "0.0", "hard invariant"),
    ("serve/spec_post_warmup_compiles", 2, "hard invariant"),
    ("serve/recalib_swaps", 0, "hard invariant"),
    ("serve/recalib_post_warmup_compiles", 1, "hard invariant"),
    ("serve/recalib_greedy_parity", "0.0", "hard invariant"),
    ("serve/recalib_r_gram_rel_err", "1e-2", "hard invariant"),
    ("dist/r_gram_rel_err", "2e-3", "hard invariant"),
])
def test_hard_invariant_violations_fail(gate, name, value, frag):
    art, check, _ = gate
    row = next(r for r in art["rows"] if r["name"] == name)
    row["value"] = value
    errs = check(art)
    assert any(name in e and frag in e for e in errs)


def test_baseline_refresh_cannot_relax_hard_invariants(tmp_path):
    """--update on a regressed artifact rewrites the bands, but the hard
    invariants live in the tool: validation still fails."""
    art = _artifact()
    next(r for r in art["rows"]
         if r["name"] == "serve/post_warmup_compiles")["value"] = 2
    path = tmp_path / "BENCH_serve.json"
    path.write_text(json.dumps(art))
    base = tmp_path / "baselines" / "BENCH_serve.json"
    assert check_bench.update_baseline(path, base) == []
    errs = check_bench.check_artifact(path, base)
    assert any("hard invariant" in e
               and "serve/post_warmup_compiles" in e for e in errs)


def test_dropped_and_unbaselined_rows_fail(gate):
    art, check, _ = gate
    dropped = copy.deepcopy(art)
    dropped["rows"] = [r for r in dropped["rows"]
                       if r["name"] != "serve/offline_tok_per_s"]
    assert any("missing from artifact" in e for e in check(dropped))
    added = copy.deepcopy(art)
    added["rows"].append({"name": "serve/new_metric", "value": "1"})
    assert any("not in baseline" in e for e in check(added))


def test_nan_null_and_suite_errors_fail(gate):
    art, check, _ = gate
    nan = copy.deepcopy(art)
    nan["rows"][3]["value"] = "nan"
    assert any("non-finite" in e for e in check(nan))
    null = copy.deepcopy(art)
    null["rows"][4]["value"] = None
    assert any("null value" in e for e in check(null))
    failed = copy.deepcopy(art)
    failed["errors"] = {"serve": "RuntimeError: boom"}
    assert any("failed in benchmarks.run" in e for e in check(failed))
    # and --update refuses to baseline a failed run
    art_path = gate[2].parent.parent / "BENCH_serve.json"
    art_path.write_text(json.dumps(failed))
    assert any("refusing" in e
               for e in check_bench.update_baseline(art_path, gate[2]))


def test_missing_baseline_is_an_error(tmp_path):
    path = tmp_path / "BENCH_serve.json"
    path.write_text(json.dumps(_artifact()))
    errs = check_bench.check_artifact(path, tmp_path / "nope.json")
    assert any("no committed baseline" in e for e in errs)


def test_update_defaults_band_for_throughput_only(gate):
    _, _, base_path = gate
    rows = json.loads(base_path.read_text())["rows"]
    assert rows["serve/paged_tok_per_s"]["kind"] == "band"
    assert rows["dist/calib_sharded_tok_per_s"]["kind"] == "band"
    assert rows["serve/warm_ttft_ms"]["kind"] == "present"
    assert rows["serve/post_warmup_compiles"]["kind"] == "present"


def test_cli_exit_codes(tmp_path, capsys, monkeypatch):
    """The CI entrypoint: exit 0 green, exit 1 on a seeded regression."""
    art_path = tmp_path / "BENCH_serve.json"
    art_path.write_text(json.dumps(_artifact()))
    bdir = tmp_path / "baselines"
    argv = ["check_bench.py", str(art_path), "--baseline-dir", str(bdir)]
    monkeypatch.setattr(sys, "argv", argv + ["--update"])
    assert check_bench.main() == 0
    monkeypatch.setattr(sys, "argv", argv)
    assert check_bench.main() == 0
    bad = _artifact()
    bad["rows"][0]["value"] = "10.0"
    art_path.write_text(json.dumps(bad))
    assert check_bench.main() == 1
    out = capsys.readouterr().out
    assert "outside" in out
