"""Traffic kind ``calib_dp``: data-parallel COALA calibration over a
``data`` mesh of ``shards`` chips (``repro.dist.calibrate``).

A job is one ``calibrate_sharded`` call over ``steps`` seeded
``TokenPipeline`` batches of ``batch`` x ``seq_len`` tokens: each chip
folds its own ``batch / shards`` rows of every batch into per-shard R
factors, and the job ends in the butterfly TSQR reduce of every R over the
mesh. Set-up runs one job, which compiles every program; the window then
runs whole jobs, each on batches of its own, until ``--seconds`` have
passed, each ended by ``block_until_ready`` on every reduced R.
``calib_tok_s`` is the tokens of those jobs over the time from the
window's opening to the end of the last.

``correct``: a job starts every R from nothing, so the last job's R must
hold, as RᵀR, the Gram of its batches' activations, which the plain
reference (``reference/calibration.py``) recomputes ``ref_rows``
sequences at a time; the number compared is the worst relative gap over
every target linear (``fold_gram_gap``). Its control puts the reference
one precision lower in the program's place: the Grams of its activations
at three bfloat16 passes (float32 at ``"high"``) stand for what the R
factors hold.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from benchmarks.chip import generate, loader, serving, weights
from benchmarks.chip.reference import calibration as ref_calib


def run(*, conf, mix, seed, seconds, ann, tracer, compiles, control,
        t_start) -> Dict[str, Any]:
    from benchmarks.chip import harness
    from repro.data.pipeline import DataConfig, TokenPipeline
    from repro.dist.calibrate import calibrate_sharded
    from repro.models.transformer import LM

    shards, steps = int(mix["shards"]), int(mix["steps"])
    mesh = jax.make_mesh((shards,), ("data",),
                         devices=jax.devices()[:shards])
    model = LM(serving.model_config(conf))
    dt = jnp.dtype(conf["dtypes"]["calibration"])
    params = weights.make(model, generate.seed31(seed, 0), dt, conf["init"])
    pipe = TokenPipeline(DataConfig(
        vocab_size=conf["model"]["vocab_size"], seq_len=int(mix["seq_len"]),
        global_batch=int(mix["batch"]), seed=generate.seed31(seed, 3)))

    def job(k: int):
        batches = [pipe.get_batch(k * steps + i) for i in range(steps)]
        rs = calibrate_sharded(model, params, batches, mesh).r_factors()
        jax.block_until_ready(rs)
        return rs

    job(0)
    before = compiles.snapshot()
    jax.config.update("jax_log_compiles", True)
    jobs: List[Dict[str, float]] = []
    t0 = time.perf_counter()
    end = t0 + seconds
    tracer.start(jobs)
    k = 1
    while True:
        j0 = time.perf_counter()
        with ann("calib.batch"):
            rs = job(k)
        now = time.perf_counter()
        jobs.append({"start": j0, "end": now})
        k += 1
        tracer.maybe_stop(jobs)
        if now >= end:
            break
    close = time.perf_counter()
    tracer.maybe_stop(jobs, force=True)
    after = compiles.snapshot()
    jax.config.update("jax_log_compiles", False)
    tokens_per_job = steps * int(mix["batch"]) * int(mix["seq_len"])
    n = len(jobs)
    e2e = {"setup_s": t0 - t_start,
           "calib_tok_s": n * tokens_per_job / (close - t0)}
    device = harness.device_info()
    record = {"jobs": jobs[tracer.first_step:tracer.last_step],
              "host_window": (tracer.t_start, tracer.t_stop)}
    info = {"setup_compiles": before, "window_s": close - t0, "jobs": n,
            "window_compiles": after["compiles"] - before["compiles"],
            "window_cache_hits": after["cache_hits"] - before["cache_hits"]}
    # the last job's batches, ``ref_rows`` sequences at a time
    rows = int(mix["ref_rows"])
    last = [pipe.get_batch((k - 1) * steps + i)["tokens"]
            for i in range(steps)]
    window = [t[i:i + rows] for t in last for i in range(0, t.shape[0], rows)]
    arch = serving.arch(conf)
    zero = {p: jnp.zeros_like(r) for p, r in rs.items()}
    grams = ref_calib.grams(arch, params, window)
    got = loader.kind("calib").fold_gap(zero, rs, grams)
    if control:
        ctrl = ref_calib.grams(arch, params, window, quant=mix["control"])
        info["program_fold_gram_gap"] = got
        got = max(float(jnp.linalg.norm(ctrl[i][tap] - g)
                        / jnp.linalg.norm(g))
                  for i, layer in enumerate(grams) for tap, g in layer.items())
    return {"e2e": e2e, "record": record, "attempted": n, "failed": 0,
            "device": device, "compared": {"fold_gram_gap": got},
            "info": info}
