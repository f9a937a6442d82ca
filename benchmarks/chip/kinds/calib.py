"""Traffic kind ``calib``: streaming COALA calibration, the Gram-free path.

Seeded ``TokenPipeline`` batches of ``batch`` x ``seq_len`` tokens go
through ``LM.capture_forward`` into a ``Calibrator``, whose ``RStreamer``
folds every target linear's input activations into its R factor by QR, all
under the mix's matmul ``precision`` in float32 (as ``calibrate_model``
runs it). Set-up folds batch 0, which compiles every eager op; the window
then folds whole batches until ``--seconds`` have passed, each ended by
``block_until_ready`` on every R. ``calib_tok_s`` is the tokens of those
batches over the window.

``correct``: RᵀR gains XᵀX for every row folded in, so the R factors at the
window's opening and at its close must differ, as RᵀR, by the Gram of the
activations of every batch folded in the window, which the plain reference
recomputes. The number compared is the worst relative gap over every
target linear. Its control folds the reference's activations at three
bfloat16 passes (float32 at ``"high"``) into the opening R factors in the
program's place.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from benchmarks.chip import generate, serving, weights
from benchmarks.chip.reference import calibration as ref_calib


@jax.jit
def _fold_gap(r_new, r_old, gram):
    """|| R_newᵀR_new - R_oldᵀR_old - G || / || G || (Frobenius)."""
    hp = jax.lax.Precision.HIGHEST
    gained = (jnp.matmul(r_new.T, r_new, precision=hp)
              - jnp.matmul(r_old.T, r_old, precision=hp))
    return jnp.linalg.norm(gained - gram) / jnp.linalg.norm(gram)


def fold_gap(r_before: Dict[str, jax.Array], r_after: Dict[str, jax.Array],
             grams: List[Dict[str, jax.Array]]) -> float:
    """Worst relative gap, over every target linear, between what its R
    gained as RᵀR and the reference Gram of the rows folded in."""
    worst = 0.0
    for path, r1 in r_after.items():
        layer, tap = ref_calib.layer_and_tap(path)
        g = _fold_gap(r1, r_before[path], grams[layer][tap])
        worst = max(worst, float(g))
    return worst


def run(*, conf, mix, seed, seconds, ann, tracer, compiles, control,
        t_start) -> Dict[str, Any]:
    from benchmarks.chip import harness
    from repro.core.calibrate import Calibrator
    from repro.data.pipeline import DataConfig, TokenPipeline
    from repro.models.transformer import LM

    model = LM(serving.model_config(conf))
    dt = jnp.dtype(conf["dtypes"]["calibration"])
    params = weights.make(model, generate.seed31(seed, 0), dt, conf["init"])
    pipe = TokenPipeline(DataConfig(
        vocab_size=conf["model"]["vocab_size"], seq_len=int(mix["seq_len"]),
        global_batch=int(mix["batch"]), seed=generate.seed31(seed, 3)))
    cal = Calibrator(dtype=dt)
    precision = mix["precision"]

    def fold(step: int) -> Dict[str, jax.Array]:
        batch = pipe.get_batch(step)
        with jax.default_matmul_precision(precision):
            model.capture_forward(params, batch, cal, compute_dtype=dt)
        rs = {p: s.r for p, s in cal.streams.items()}
        jax.block_until_ready(rs)
        return rs

    r_open = fold(0)
    before = compiles.snapshot()
    jax.config.update("jax_log_compiles", True)
    batches: List[Dict[str, float]] = []
    t0 = time.perf_counter()
    end = t0 + seconds
    tracer.start(batches)
    step = 1
    while True:
        b0 = time.perf_counter()
        with ann("calib.batch"):
            rs = fold(step)
        now = time.perf_counter()
        batches.append({"start": b0, "end": now})
        step += 1
        tracer.maybe_stop(batches)
        if now >= end:
            break
    close = time.perf_counter()
    tracer.maybe_stop(batches, force=True)
    after = compiles.snapshot()
    jax.config.update("jax_log_compiles", False)
    tokens_per_batch = int(mix["batch"]) * int(mix["seq_len"])
    n = len(batches)
    e2e = {"setup_s": t0 - t_start,
           "calib_tok_s": n * tokens_per_batch / (close - t0)}
    device = harness.device_info()
    widths = {p: int(r.shape[-1]) for p, r in rs.items()}
    record = {"batches": batches[tracer.first_step:tracer.last_step],
              "model": conf["model"], "tokens_per_batch": tokens_per_batch,
              "fold_rows": int(cal.max_tokens), "widths": widths,
              "host_window": (tracer.t_start, tracer.t_stop)}
    info = {"setup_compiles": before, "window_s": close - t0, "batches": n,
            "window_compiles": after["compiles"] - before["compiles"],
            "window_cache_hits": after["cache_hits"] - before["cache_hits"]}
    r_close = rs
    del rs
    cal.reset()
    arch = serving.arch(conf)
    window = [pipe.get_batch(k)["tokens"] for k in range(1, n + 1)]
    grams = ref_calib.grams(arch, params, window)
    got = fold_gap(r_open, r_close, grams)
    if control:
        del r_close
        r_ctrl = ref_calib.folds(arch, params, window, r_open,
                                 mix["control"], int(cal.max_tokens))
        info["program_fold_gram_gap"] = got
        got = fold_gap(r_open, r_ctrl, grams)
    return {"e2e": e2e, "record": record, "attempted": n, "failed": 0,
            "device": device, "compared": {"fold_gram_gap": got},
            "info": info}
