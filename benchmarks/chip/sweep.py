"""Knee of an open-loop serving cell: the highest rate its engine sustains.

    python3 benchmarks/chip/sweep.py --workload smollm_135m.chat_poisson \
        --seed 5 --saturate 170 --fractions 0.8,1.0 --window 150

One engine and one set-up, then:

1. saturation: the cell's mix offered at ``--overload`` requests a second,
   far above what the engine serves, for ``--saturate`` seconds. Once the
   first ``--lead`` seconds have passed (the running set filled and its
   lengths mixed), the output tokens completed per second are the
   engine's capacity at this mix, and the knee is that capacity over the
   mix's mean output length: in steady state every admission is one
   completion, so prefill is counted at the rate it comes;
2. at each ``--fractions`` of that knee, the open loop as the cell runs
   it, with the mix's pre-roll and a ``--window`` several request
   lifetimes long, the waiting queue and running set sampled through it:
   a rate is sustained when its offered output tokens per second stay
   below the capacity and the queue stays flat.

Prints one JSON line per phase. The cell's fixed rate is set from it by
hand, in its traffic file. Not part of the benchmark's runs.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]


def _drain(engine) -> None:
    """End every request after its next token, so that the next phase
    starts from an empty engine without serving the backlog."""
    engine.scheduler.waiting.clear()
    for r in engine.scheduler.running:
        r.max_new_tokens = len(r.out_tokens) + 1
    while engine.has_work():
        engine.step()
    engine.reset_metrics()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--overload", type=float, default=3.0)
    ap.add_argument("--saturate", type=float, default=170.0)
    ap.add_argument("--lead", type=float, default=50.0)
    ap.add_argument("--fractions", default="0.8,1.0")
    ap.add_argument("--window", type=float, default=150.0)
    args = ap.parse_args(argv)

    import numpy as np
    from benchmarks.chip import generate, harness, loader, serving, stats
    from benchmarks.chip import trace as chiptrace
    cell = loader.cell(loader.benchmark(), args.workload)
    harness.require_chip(int(cell["chips"]))
    harness.enable_compile_cache()
    conf, mix = loader.config(cell["config"]), loader.traffic(cell["traffic"])
    kind = loader.kind(mix["kind"])
    _, _, engine = serving.build(conf, mix, args.seed)
    vocab = conf["model"]["vocab_size"]
    serving.warm(engine, mix, vocab)
    ann = harness.annotator(False)
    mean_out = float(generate.quantiles(mix["output"], 4096).mean())

    samples = []
    step = serving.Window.step

    def sampled(self):
        done = step(self)
        samples.append((time.perf_counter(),
                        len(self.engine.scheduler.waiting),
                        len(self.engine.scheduler.running)))
        return done

    serving.Window.step = sampled

    def tokens(res, lo):
        return sum(sum(lo <= s <= res["close"] for s in t.stamps)
                   for t in res["window"].tracked)

    specs = kind.requests(dict(mix, rate_per_s=args.overload, preroll_s=0),
                          args.seed, args.saturate, vocab)
    res = kind.loop(engine, specs, args.saturate, ann,
                    chiptrace.Tracer(False, args.saturate))
    lo = res["open"] + args.lead
    capacity = tokens(res, lo) / (res["close"] - lo)
    knee = capacity / mean_out
    print(json.dumps({
        "phase": "saturation", "offered_req_s": args.overload,
        "capacity_tok_s": capacity, "mean_output": mean_out,
        "knee_req_s": knee,
        "running_mean": float(np.mean([r for t, _, r in samples
                                       if t >= lo]))}), flush=True)
    _drain(engine)

    for k, frac in enumerate(float(f) for f in args.fractions.split(",")):
        rate = round(frac * knee, 3)
        samples.clear()
        specs = kind.requests(dict(mix, rate_per_s=rate),
                              args.seed + 1 + k, args.window, vocab)
        res = kind.loop(engine, specs, args.window, ann,
                        chiptrace.Tracer(False, args.window),
                        float(mix.get("preroll_s", 0.0)))
        o = res["open"]
        every = max(1, len(samples) // 40)
        print(json.dumps({
            "phase": "open", "fraction_of_knee": frac, "rate_per_s": rate,
            "offered_tok_s": rate * mean_out,
            "served_tok_s": tokens(res, o) / (res["close"] - o),
            "requests_due": res["attempted"],
            "ttft_p90_ms": 1e3 * stats.percentile(res["ttft_s"], 90),
            "ttft_p50_ms": 1e3 * stats.percentile(res["ttft_s"], 50),
            "itl_p95_ms": 1e3 * stats.percentile(res["itl_s"], 95),
            "itl_p50_ms": 1e3 * stats.percentile(res["itl_s"], 50),
            "t_waiting_running": [(round(t - o, 1), w, r)
                                  for t, w, r in samples[::every]]}),
            flush=True)
        _drain(engine)
    return 0


if __name__ == "__main__":
    sys.exit(main())
