"""Traffic kind ``open``: independent users under an open loop.

Request ``i`` is due at a time fixed on the clock, whatever the system is
doing: Poisson gaps at the mix's ``rate_per_s``. The first ``preroll_s``
seconds of that traffic fill the engine before the window opens, so that
the window sees a steady state, not a ramp from empty; the pre-roll counts
as set-up. The window drives ``ContinuousEngine.submit`` / ``step``,
submitting each request between steps once it is due, and judges:

- ``ttft_p90_ms``: over every request due in the window, from its due time
  to the return of the ``step()`` that produced its first token; a request
  with no first token when the window closes counts at close - due;
- and, for the per-layer readers (``itl_p95``, ``itl_p50``), every gap
  between consecutive tokens of a request, both stamped in the traced
  span of the window.

Mix keys: ``rate_per_s``, ``preroll_s``, ``strata``, ``prompt`` and ``output`` (length
distributions, ``generate.quantiles``), ``temperature``, ``greedy_every``,
``engine`` (``max_running``, ``block_size``, ``num_blocks``,
``prefix_cache``), ``control``, ``check``, ``trace_seconds``.
"""
from __future__ import annotations

import math
import time
from typing import Any, Callable, Dict, List

import jax
import numpy as np

from benchmarks.chip import generate, serving, stats


def requests(mix: Dict[str, Any], seed: int, seconds: float,
             vocab: int) -> List[generate.Spec]:
    """The pre-roll's requests, then the window's: ``floor(rate x
    length)`` of each, so that every seed sends the window the same set of
    sizes and inter-arrival gaps, in another order (``generate.stratified``
    with the mix's ``strata``: every run of that many requests holds one
    gap, one prompt and one output length from each band). ``due_s``
    counts from the window's opening, so the pre-roll's requests are due
    at negative times."""
    pre = float(mix.get("preroll_s", 0.0))
    rate = float(mix["rate_per_s"])
    strata = int(mix.get("strata", 1))
    rng = generate.rng_for(seed, 1)
    out: List[generate.Spec] = []
    for start, length in ((-pre, pre), (0.0, float(seconds))):
        n = int(math.floor(rate * length))
        if n == 0:
            continue
        u = (np.arange(n) + 0.5) / n
        due = start + np.cumsum(generate.stratified(
            rng, -np.log1p(-u) / rate, strata))
        plens = generate.stratified(
            rng, generate.quantiles(mix["prompt"], n), strata)
        olens = generate.stratified(
            rng, generate.quantiles(mix["output"], n), strata)
        out += [generate.request(mix, rng, len(out) + i, due[i], plens[i],
                                 olens[i], vocab) for i in range(n)]
    return out


def loop(engine, specs: List[generate.Spec], seconds: float, ann: Callable,
         tracer, preroll: float = 0.0) -> Dict[str, Any]:
    """Submit each request once its due time has come, between steps; the
    window opens ``preroll`` seconds after the first arrivals may come."""
    w = serving.Window(engine, ann)
    i, n = 0, len(specs)
    t0 = time.perf_counter() + preroll
    end = t0 + seconds
    opened = preroll <= 0
    if opened:
        tracer.start(w.steps)
    while True:
        now = time.perf_counter()
        if now >= end:
            break
        if not opened and now >= t0:
            opened = True
            tracer.start(w.steps)
        with ann("harness.submit"):
            while i < n and t0 + specs[i].due_s <= now:
                w.submit(specs[i], t0 + specs[i].due_s)
                i += 1
        if engine.has_work():
            w.step()
        else:
            nxt = t0 + specs[i].due_s if i < n else end
            if not opened:
                nxt = min(nxt, t0)
            with ann("harness.wait"):
                time.sleep(max(0.0, min(nxt, end) - time.perf_counter()))
        if opened:
            tracer.maybe_stop(w.steps)
    close = time.perf_counter()
    tracer.maybe_stop(w.steps, force=True)
    due = [t0 + s.due_s for s in specs]
    first = [None] * n
    for t in w.tracked:
        first[t.spec.index] = t.first
    inside = [k for k in range(n) if due[k] >= t0]
    ttft = stats.ttfts([due[k] for k in inside], [first[k] for k in inside],
                       close)
    gaps = stats.token_gaps([t.stamps for t in w.tracked], t0, close)
    late = [t.submitted - t.due for t in w.tracked]
    return {"window": w, "open": t0, "close": close, "ttft_s": ttft,
            "itl_s": gaps, "attempted": len(ttft), "late_s": late}


def run(*, conf, mix, seed, seconds, ann, tracer, compiles, control,
        t_start) -> Dict[str, Any]:
    from benchmarks.chip import harness
    _, params, engine = serving.build(conf, mix, seed)
    vocab = conf["model"]["vocab_size"]
    info: Dict[str, Any] = {"warmup": serving.warm(engine, mix, vocab)}
    before = compiles.snapshot()
    # nothing should compile in the window; if anything does, name it
    jax.config.update("jax_log_compiles", True)
    res = loop(engine, requests(mix, seed, seconds, vocab), seconds, ann,
               tracer, float(mix.get("preroll_s", 0)))
    after = compiles.snapshot()
    jax.config.update("jax_log_compiles", False)
    w: serving.Window = res["window"]
    span = res["close"] - res["open"]
    e2e = {"setup_s": res["open"] - t_start,
           "ttft_p90_ms": 1e3 * stats.percentile(res["ttft_s"], 90)}
    info.update(
        setup_compiles=before, window_s=span,
        window_compiles=after["compiles"] - before["compiles"],
        window_cache_hits=after["cache_hits"] - before["cache_hits"],
        finished=sum(t.done for t in w.tracked), submitted=len(w.tracked),
        waiting_at_close=len(engine.scheduler.waiting),
        running_at_close=len(engine.scheduler.running),
        preemptions=sum(t.req.preemptions for t in w.tracked),
        itl_samples=len(res["itl_s"]),
        late_s_max=max(res["late_s"], default=0.0))
    failed = w.refused + sum(not t.req.logits_finite for t in w.tracked)
    device = harness.device_info()
    steps = w.steps[tracer.first_step:tracer.last_step]
    # the gaps of the traced span only: the profiler stalls the host loop
    # for seconds when it stops, after that span
    traced_gaps = stats.token_gaps([t.stamps for t in w.tracked],
                                   tracer.t_start, tracer.t_stop) \
        if tracer.t_stop is not None else []
    record = {"steps": steps, "model": conf["model"], "itl_s": traced_gaps,
              "host_window": (tracer.t_start, tracer.t_stop)}
    serving.free(engine)
    del engine
    compared = serving.compare(conf, mix, params, w, seed, control, info)
    return {"e2e": e2e, "record": record, "attempted": res["attempted"],
            "failed": failed, "device": device, "compared": compared,
            "info": info}
