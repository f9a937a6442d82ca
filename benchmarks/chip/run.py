"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

from the root of a checkout. Prints the cold/warm compile split and the
set-up's own numbers on earlier lines, and as its last line of standard
output one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device``, with ``--trace 1`` ``breakdown``, and last
``compared``: each number the correctness check compared, beside its limit.
The same numbers are the last lines of standard error. Exits nonzero, and
prints no result, where JAX finds no TPU or fewer chips than the cell asks
for. ``--control 1`` puts the control of the check (the reference in the
next lower precision) in the program's place: the numbers compared are the
control's, ``correct`` must come out false, and the program's own readings
go under ``info``. The benchmark's own runs leave it off.
"""
import argparse
import json
import os
import sys
import time

T0 = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
# the checkout's root (for ``benchmarks.chip``) and the program's sources;
# not this directory, whose ``trace.py`` would shadow the standard library's
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.chip import harness
    harness.T_START = T0
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), control=bool(args.control))
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    setup = result["info"].get("setup_compiles", {})
    print(f"# set-up compiled {setup.get('compiles', 0)} programs in "
          f"{setup.get('compile_s', 0.0):.1f} s, "
          f"{setup.get('cache_hits', 0)} loaded from the persistent cache; "
          f"the window compiled {result['info'].get('window_compiles', 0)}",
          flush=True)
    for name, c in result["compared"].items():
        print(f"compared {name} = {c['value']!r} (pass if {c['pass_if']} "
              f"{c['limit']!r})", file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
