"""Compression launcher: calibrate → COALA/baseline → evaluate → save.

With ``--mesh data=N``, calibration shards activation rows over the data
axis and reduces per-shard R factors with the distributed butterfly TSQR
(``repro.dist.calibrate``); on a single device it streams through the
RStreamer. Either way the full activation matrix X never exists.

  PYTHONPATH=src python -m repro.launch.compress --arch llama3_1b --smoke \
      --method coala --ratio 0.6 --lam 4 [--mesh data=8]
"""
import argparse
import json
import os
import sys


def _peek_mesh(argv):
    """Parse ``--mesh data=N`` from raw argv (``{}`` when absent/malformed).

    Must run before the first jax import: the fake-device count is locked at
    jax initialization, so ``main()``'s argparse is too late to raise it.
    """
    val = ""
    for i, a in enumerate(argv):
        if a == "--mesh" and i + 1 < len(argv):
            val = argv[i + 1]
        elif a.startswith("--mesh="):
            val = a.split("=", 1)[1]
    out = {}
    for part in val.split(","):
        if "=" in part:
            name, _, size = part.partition("=")
            try:
                out[name.strip()] = int(size)
            except ValueError:
                pass
    return out


_MESH = _peek_mesh(sys.argv)
_MESH_DEVICES = 1
for _s in _MESH.values():
    _MESH_DEVICES *= _s
if _MESH_DEVICES > 1 and "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               f" --xla_force_host_platform_device_count="
                               f"{_MESH_DEVICES}").strip()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.config import CompressConfig, TrainConfig  # noqa: E402
from repro.configs import get_config, get_smoke_config  # noqa: E402
from repro.core.calibrate import calibrate_model  # noqa: E402
from repro.core.compress import compress_model, compression_summary  # noqa: E402
from repro import compile_cache  # noqa: E402
from repro.ckpt import CheckpointManager  # noqa: E402
from repro.data import DataConfig, TokenPipeline  # noqa: E402
from repro.dist.calibrate import calibrate_sharded  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.common import CPU_CTX  # noqa: E402
from repro.obs import numerics, trace as obs_trace  # noqa: E402
from repro.train.train_loop import make_train_state, make_train_step  # noqa: E402

CALIB_BATCH = 8          # rows per calibration batch (the TokenPipeline below)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3_1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--method", default="coala",
                    choices=["coala", "svd", "svd_llm", "svd_llm_v2", "asvd"])
    ap.add_argument("--ratio", type=float, default=0.6)
    ap.add_argument("--lam", type=float, default=4.0)
    ap.add_argument("--mu", type=float, default=-1.0)
    ap.add_argument("--rsvd", action="store_true")
    ap.add_argument("--calib-batches", type=int, default=4)
    ap.add_argument("--pretrain-steps", type=int, default=100,
                    help="train a base model first (no public weights offline)")
    ap.add_argument("--ckpt-in", default="", help="restore base model instead")
    ap.add_argument("--ckpt-out", default="")
    ap.add_argument("--mesh", default="",
                    help="shard calibration rows, e.g. 'data=8' (fake CPU "
                         "devices are forced to match before jax init; N "
                         "must be a power of two dividing the calibration "
                         "batch)")
    ap.add_argument("--numerics-report", action="store_true",
                    help="print per-layer numerical health after "
                         "calibration: cond(R) with warn/fail grading, "
                         "insufficient-data flags, and achieved residual "
                         "vs. the attainable bound (obs/numerics.py; works "
                         "for both single-device and --mesh calibration)")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome/Perfetto trace_event JSON of the "
                         "calibration/compression spans to this path")
    args = ap.parse_args()
    compile_cache.enable()
    if args.trace_out:
        obs_trace.enable()
    if args.mesh:
        # fail fast (before minutes of pretrain/eval): _peek_mesh swallows
        # malformed values, so a typo would silently fall back to the
        # single-device path, and bad shard counts would only crash deep
        # inside calibrate_sharded after the expensive phase
        if not _MESH or set(_MESH) != {"data"}:
            ap.error(f"--mesh {args.mesh!r} not understood; expected "
                     f"'data=N' (calibration shards over the data axis)")
        n_shards = _MESH["data"]
        if n_shards < 1 or n_shards & (n_shards - 1):
            ap.error(f"--mesh data={n_shards}: shard count must be a power "
                     f"of two (butterfly TSQR pairing)")
        if CALIB_BATCH % n_shards:
            ap.error(f"--mesh data={n_shards}: must divide the calibration "
                     f"batch of {CALIB_BATCH} rows")
        if len(jax.devices()) < n_shards:
            # a pre-set XLA_FLAGS device count suppresses the import-time
            # forcing — surface that now, not after pretrain/eval
            ap.error(f"--mesh data={n_shards}: only {len(jax.devices())} "
                     f"devices visible (XLA_FLAGS already set?)")

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    pipe = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size, seq_len=64,
                                    global_batch=CALIB_BATCH, seed=11), cfg)

    tcfg = TrainConfig(lr=3e-3, warmup_steps=5, total_steps=args.pretrain_steps,
                       schedule="cosine", compute_dtype="float32")
    state = make_train_state(model, tcfg, jax.random.PRNGKey(0))
    if args.ckpt_in:
        state, _ = CheckpointManager(args.ckpt_in).restore(state)
    else:
        step = jax.jit(make_train_step(model, tcfg, CPU_CTX))
        for i in range(args.pretrain_steps):
            state, _ = step(state, pipe.get_batch(i))
    params = state["params"]

    def eval_ce(p):
        return float(np.mean([float(model.loss(p, pipe.get_batch(1000 + i),
                                               compute_dtype=jnp.float32)[0])
                              for i in range(4)]))

    base_ce = eval_ce(params)
    calib_batches = [pipe.get_batch(2000 + i)
                     for i in range(args.calib_batches)]
    if _MESH.get("data", 1) > 1:
        mesh = make_mesh((_MESH["data"],), ("data",))
        cal = calibrate_sharded(model, params, calib_batches, mesh,
                                axis="data")
        print(f"# sharded calibration: data={_MESH['data']} "
              f"(butterfly TSQR reduce)")
    else:
        cal = calibrate_model(model, params, calib_batches)
    if args.numerics_report:
        # duck-typed over Calibrator / ShardedCalibration: the same check
        # covers single-device and butterfly-reduced mesh calibration
        health = numerics.check_calibration(cal)
        print("# calibration numerics")
        print(numerics.format_report(health))
    ccfg = CompressConfig(method=args.method, ratio=args.ratio, lam=args.lam,
                          mu=args.mu, use_rsvd=args.rsvd)
    cparams, reports = compress_model(model, params, cal, ccfg)
    if args.numerics_report:
        print("# projection residual vs attainable bound")
        print(numerics.format_report(numerics.check_compression(reports)))
    s = compression_summary(reports)
    s.update(method=args.method, base_ce=base_ce, compressed_ce=eval_ce(cparams))
    print(json.dumps(s, indent=1))
    if args.ckpt_out:
        CheckpointManager(args.ckpt_out).save(0, {"params": cparams})
        print("saved to", args.ckpt_out)
    if args.trace_out:
        n = obs_trace.save(args.trace_out)
        print(f"wrote {n} trace events to {args.trace_out}")


if __name__ == "__main__":
    main()
