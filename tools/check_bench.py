"""CI perf-regression gate over BENCH_*.json artifacts.

Validates the JSON artifacts ``benchmarks.run --json`` writes against
committed baselines in ``benchmarks/baselines/`` and exits nonzero with one
line per problem. Pure stdlib — no jax, no pip installs; CI runs it right
after the bench smoke steps, so a regression fails the PR instead of
landing as a quietly worse artifact.

Checks, per artifact:

  1. **Integrity** — the file parses, its ``errors`` map (written by
     ``benchmarks.run`` when a suite raises or emits no rows) is empty, row
     names are unique, and no row value is null/empty/NaN/inf.
  2. **Schema completeness, both ways** — every baseline row is present in
     the artifact (a silently dropped metric is a regression) and every
     artifact row is present in the baseline (a new metric must be
     baselined, not invisible to the gate).
  3. **Hard invariants** — non-negotiable acceptance rows enforced from
     this file, not the baseline, so editing a baseline can never relax
     them: ``serve/post_warmup_compiles == 0``, ``serve/slo_goodput ==
     1`` (uncontended smoke traffic must meet its generous SLOs — a
     goodput dip on an idle box is an accounting bug, not load),
     ``serve/paged_vs_gather_decode_speedup >= 1``, the speculative
     rows (``serve/spec_greedy_parity == 1``, ``serve/spec_accept_rate >
     0``, ``serve/spec_decode_speedup >= 1``,
     ``serve/spec_post_warmup_compiles == 0``), the live-recalibration
     rows (``serve/recalib_swaps >= 1`` — at least one bound-cleared
     hot-swap, ``serve/recalib_post_warmup_compiles == 0`` — swaps never
     retrace, ``serve/recalib_greedy_parity == 1`` — identity swaps are
     token-exact, ``serve/recalib_r_gram_rel_err < 1e-3`` — traffic
     calibration matches offline replay) and ``dist/r_gram_rel_err <
     1e-3`` (each required whenever the artifact ran that suite).
  4. **Baseline comparisons** — each baseline row carries a ``kind``:
       * ``band``: value within ±``band_pct``% of the baseline value
         (default 40 — CPU CI wall times are noisy; per-row ``band_pct``
         overrides tighten or loosen it).
       * ``min`` / ``max``: one-sided floor/ceiling.
       * ``present``: the row must exist with a sane value, nothing more
         (latency rows on shared CI hardware live here).

Refreshing a baseline after an intentional perf change::

    PYTHONPATH=src python -m benchmarks.run serve --smoke --json BENCH_serve.json
    python tools/check_bench.py --update BENCH_serve.json

``--update`` rewrites the committed baseline from the artifact: existing
rows keep their kind and overrides (only the reference value moves), new
rows default to ``band`` for throughput (``*tok_per_s`` / ``*req_per_s``)
and ``present`` otherwise, and rows the artifact no longer emits are
dropped. Commit the diff with the PR that changed the numbers; the
workflow is documented in docs/benchmarks.md.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
BASELINE_DIR = ROOT / "benchmarks" / "baselines"
DEFAULT_BAND_PCT = 40.0

# suite name -> rows the gate enforces whenever that suite ran, regardless
# of what any baseline says (op, threshold)
HARD_INVARIANTS = {
    "serve": [
        ("serve/post_warmup_compiles", "==", 0.0),
        ("serve/slo_goodput", "==", 1.0),
        ("serve/paged_vs_gather_decode_speedup", ">=", 1.0),
        ("serve/spec_greedy_parity", "==", 1.0),
        ("serve/spec_accept_rate", ">", 0.0),
        ("serve/spec_decode_speedup", ">=", 1.0),
        ("serve/spec_post_warmup_compiles", "==", 0.0),
        ("serve/recalib_swaps", ">=", 1.0),
        ("serve/recalib_post_warmup_compiles", "==", 0.0),
        ("serve/recalib_greedy_parity", "==", 1.0),
        ("serve/recalib_r_gram_rel_err", "<", 1e-3),
    ],
    "dist": [
        ("dist/r_gram_rel_err", "<", 1e-3),
    ],
}

def _rel(path: pathlib.Path) -> str:
    try:
        return str(path.relative_to(ROOT))
    except ValueError:
        return str(path)


_OPS = {
    "==": lambda v, t: v == t,
    "<": lambda v, t: v < t,
    "<=": lambda v, t: v <= t,
    ">=": lambda v, t: v >= t,
    ">": lambda v, t: v > t,
}


def _num(value):
    """Parse a row value; returns (float, None) or (None, reason)."""
    if value is None:
        return None, "null value"
    if isinstance(value, bool):
        return float(value), None
    if isinstance(value, (int, float)):
        v = float(value)
    else:
        s = str(value).strip()
        if not s:
            return None, "empty value"
        try:
            v = float(s)
        except ValueError:
            return None, "non-numeric"
    if not math.isfinite(v):
        return None, f"non-finite value {value!r}"
    return v, None


def _rows_by_name(artifact: dict, errors: list, label: str) -> dict:
    rows = {}
    for row in artifact.get("rows", []):
        name = row.get("name")
        if not name:
            errors.append(f"{label}: row without a name: {row!r}")
            continue
        if name in rows:
            errors.append(f"{label}: duplicate row {name}")
        rows[name] = row.get("value")
    return rows


def default_kind(name: str) -> str:
    return ("band" if name.endswith(("tok_per_s", "req_per_s"))
            else "present")


def check_artifact(path: pathlib.Path, baseline_path: pathlib.Path) -> list:
    label = path.name
    try:
        artifact = json.loads(path.read_text())
    except (OSError, ValueError) as e:
        return [f"{label}: unreadable artifact: {e}"]
    errors: list = []
    for suite, msg in (artifact.get("errors") or {}).items():
        errors.append(f"{label}: suite {suite} failed in benchmarks.run: "
                      f"{msg}")
    rows = _rows_by_name(artifact, errors, label)
    if not rows:
        errors.append(f"{label}: artifact has no rows")
        return errors

    # integrity: every value must be sane (finite if it parses at all)
    numeric: dict = {}
    for name, value in rows.items():
        v, why = _num(value)
        if v is None and why != "non-numeric":
            errors.append(f"{label}: row {name}: {why}")
        elif v is not None:
            numeric[name] = v

    # hard invariants: enforced from this file whenever the suite ran
    for suite in artifact.get("benchmarks", []):
        for name, op, thresh in HARD_INVARIANTS.get(suite, []):
            if name not in rows:
                errors.append(f"{label}: hard-invariant row {name} missing "
                              f"(suite {suite} ran)")
            elif name not in numeric:
                errors.append(f"{label}: hard-invariant row {name} is not "
                              f"numeric: {rows[name]!r}")
            elif not _OPS[op](numeric[name], thresh):
                errors.append(f"{label}: hard invariant violated: {name} = "
                              f"{numeric[name]:g}, required {op} {thresh:g}")

    if not baseline_path.exists():
        errors.append(
            f"{label}: no committed baseline at {_rel(baseline_path)} — "
            f"generate the artifact and run tools/check_bench.py "
            f"--update {path}")
        return errors
    baseline = json.loads(baseline_path.read_text())
    base_rows = baseline.get("rows", {})
    band_default = float(baseline.get("default_band_pct", DEFAULT_BAND_PCT))

    # schema completeness, both directions
    for name in sorted(set(base_rows) - set(rows)):
        errors.append(f"{label}: baseline row {name} missing from artifact")
    for name in sorted(set(rows) - set(base_rows)):
        errors.append(f"{label}: row {name} not in baseline — rerun "
                      f"tools/check_bench.py --update after reviewing it")

    for name, spec in sorted(base_rows.items()):
        if name not in rows:
            continue
        kind = spec.get("kind", "present")
        if kind == "present":
            continue
        if name not in numeric:
            errors.append(f"{label}: row {name} must be numeric for "
                          f"kind={kind}, got {rows[name]!r}")
            continue
        v = numeric[name]
        ref = float(spec.get("value", 0.0))
        if kind == "band":
            pct = float(spec.get("band_pct", band_default))
            lo, hi = ref * (1 - pct / 100), ref * (1 + pct / 100)
            if ref < 0:
                lo, hi = hi, lo
            if not lo <= v <= hi:
                errors.append(
                    f"{label}: {name} = {v:g} outside ±{pct:g}% of "
                    f"baseline {ref:g} [{lo:g}, {hi:g}]")
        elif kind == "min":
            if v < ref:
                errors.append(f"{label}: {name} = {v:g} below baseline "
                              f"floor {ref:g}")
        elif kind == "max":
            if v > ref:
                errors.append(f"{label}: {name} = {v:g} above baseline "
                              f"ceiling {ref:g}")
        else:
            errors.append(f"{label}: baseline row {name} has unknown "
                          f"kind {kind!r}")
    return errors


def update_baseline(path: pathlib.Path, baseline_path: pathlib.Path) -> list:
    try:
        artifact = json.loads(path.read_text())
    except (OSError, ValueError) as e:
        return [f"{path.name}: unreadable artifact: {e}"]
    errors: list = []
    for suite, msg in (artifact.get("errors") or {}).items():
        errors.append(f"{path.name}: refusing to baseline a failed run "
                      f"(suite {suite}: {msg})")
    rows = _rows_by_name(artifact, errors, path.name)
    if errors:
        return errors
    old = {}
    if baseline_path.exists():
        old = json.loads(baseline_path.read_text()).get("rows", {})
    out = {}
    for name in sorted(rows):
        v, _ = _num(rows[name])
        spec = dict(old.get(name, {"kind": default_kind(name)}))
        if spec.get("kind") in ("band", "min", "max"):
            if v is None:
                errors.append(f"{path.name}: row {name} is kind="
                              f"{spec['kind']} but not numeric: "
                              f"{rows[name]!r}")
                continue
            spec["value"] = v
        else:
            spec.pop("value", None)
        out[name] = spec
    if errors:
        return errors
    baseline_path.parent.mkdir(parents=True, exist_ok=True)
    doc = {"source": path.name,
           "benchmarks": artifact.get("benchmarks", []),
           "smoke": artifact.get("smoke", False),
           "default_band_pct": DEFAULT_BAND_PCT,
           "rows": out}
    baseline_path.write_text(json.dumps(doc, indent=1) + "\n")
    print(f"wrote {_rel(baseline_path)} ({len(out)} rows)")
    return []


def main() -> int:
    ap = argparse.ArgumentParser(
        description="validate BENCH_*.json against committed baselines")
    ap.add_argument("artifacts", nargs="+",
                    help="BENCH_*.json files written by benchmarks.run")
    ap.add_argument("--baseline-dir", default=str(BASELINE_DIR),
                    help="directory of committed baselines (default: "
                         "benchmarks/baselines/)")
    ap.add_argument("--update", action="store_true",
                    help="rewrite the baselines from the artifacts instead "
                         "of validating (intentional perf changes)")
    args = ap.parse_args()
    errors = []
    for a in args.artifacts:
        path = pathlib.Path(a)
        baseline_path = pathlib.Path(args.baseline_dir) / path.name
        if args.update:
            errors += update_baseline(path, baseline_path)
        else:
            errors += check_artifact(path, baseline_path)
    for e in errors:
        print(f"ERROR: {e}")
    if not errors and not args.update:
        print(f"bench OK: {len(args.artifacts)} artifact(s) within baseline "
              "bands, hard invariants hold")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
