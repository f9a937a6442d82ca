"""Production training launcher.

On the CPU container this runs reduced configs end-to-end; on a real fleet
the same script runs under `jax.distributed` (one process per host — set
--coordinator for multi-host initialization).

Fault-tolerance model (synchronous SPMD at 1000+ nodes):
  * atomic async checkpoints every --ckpt-every steps (tmp+rename; a crash
    mid-write never corrupts the restore target);
  * on ANY failure the job scheduler restarts this launcher; it resumes from
    the latest checkpoint, and the step-indexed data pipeline replays the
    exact token stream — no state beyond the step counter;
  * elastic restarts: the checkpoint stores unsharded leaves, restore
    device_puts them under the NEW mesh's shardings — pod/data/model sizes
    may change between runs (ZeRO resharding for free);
  * stragglers: synchronous SPMD makes the step time the max over chips. The
    deployment recipe is (a) checkpoint-restart onto a hot-spare pod when a
    chip degrades (swap the failed pod's slice address, resume), (b) the
    cross-pod gradient hop is int8-compressed (--grad-compress) so slow DCN
    links stop dominating, (c) XLA's latency-hiding scheduler overlaps the
    FSDP all-gathers with compute (enabled via flags below).

  PYTHONPATH=src python -m repro.launch.train --arch smollm_135m --smoke \
      --steps 50 --mesh 1,1,1
"""
import os

# latency-hiding scheduler: overlap collectives with compute on real hw
os.environ.setdefault(
    "XLA_FLAGS",
    os.environ.get("XLA_FLAGS", "") +
    " --xla_tpu_enable_latency_hiding_scheduler=true"
    if os.environ.get("JAX_PLATFORMS", "") == "tpu" else
    os.environ.get("XLA_FLAGS", ""))

import argparse  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.ckpt import CheckpointManager  # noqa: E402
from repro.config import TrainConfig  # noqa: E402
from repro.configs import get_config, get_smoke_config  # noqa: E402
from repro.data import DataConfig, TokenPipeline  # noqa: E402
from repro.dist.sharding import (batch_axes_of, batch_specs,  # noqa: E402
                                 to_named, train_state_specs)
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.common import CPU_CTX, ParallelCtx  # noqa: E402
from repro.train import grad_compress as gc  # noqa: E402
from repro.train.train_loop import (make_train_state,  # noqa: E402
                                    make_train_step)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_135m")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--mesh", default="1,1,1",
                    help="pod,data,model sizes (1,1,1 = single device)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--schedule", default="cosine",
                    choices=["cosine", "wsd", "const"])
    ap.add_argument("--remat", default="none", choices=["none", "dots", "full"])
    ap.add_argument("--grad-compress", action="store_true",
                    help="int8+EF cross-pod gradient reduction")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--coordinator", default="",
                    help="host:port for jax.distributed multi-host init")
    ap.add_argument("--process-id", type=int, default=0)
    ap.add_argument("--num-processes", type=int, default=1)
    args = ap.parse_args()
    compile_cache.enable()

    if args.coordinator:
        jax.distributed.initialize(args.coordinator, args.num_processes,
                                   args.process_id)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    tcfg = TrainConfig(lr=args.lr, warmup_steps=max(5, args.steps // 20),
                       total_steps=args.steps, schedule=args.schedule,
                       microbatches=args.microbatches, remat=args.remat,
                       grad_compress_pods=args.grad_compress,
                       compute_dtype="float32" if args.smoke else "bfloat16")

    shape = tuple(int(x) for x in args.mesh.split(","))
    multi = shape[0] * shape[1] * shape[2] > 1
    if multi:
        mesh = make_mesh(shape, ("pod", "data", "model"))
        ctx = ParallelCtx(mesh=mesh, batch_axes=batch_axes_of(mesh),
                          shard_map_moe=cfg.uses_moe)
    else:
        mesh, ctx = None, CPU_CTX

    pipe = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size,
                                    seq_len=args.seq,
                                    global_batch=args.batch), cfg)
    state = make_train_state(model, tcfg, jax.random.PRNGKey(tcfg.seed))
    if tcfg.grad_compress_pods and multi:
        state["err"] = gc.init_error_state(state["params"], shape[0])

    step_fn = make_train_step(model, tcfg, ctx, mesh=mesh)
    if multi:
        sspecs = train_state_specs(cfg, state, mesh, strategy="fsdp")
        bspecs = batch_specs(cfg, pipe.get_batch(0), mesh)
        step_fn = jax.jit(step_fn,
                          in_shardings=(to_named(sspecs, mesh),
                                        to_named(bspecs, mesh)),
                          # pin the output state to the same shardings so the
                          # step round-trips (XLA would otherwise pick its
                          # own layout for some leaves and poison step 2)
                          out_shardings=(to_named(sspecs, mesh), None),
                          donate_argnums=0)
        state = jax.device_put(state, to_named(sspecs, mesh))
    else:
        step_fn = jax.jit(step_fn, donate_argnums=0)

    mgr = None
    start = 0
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, keep=3)
        if mgr.latest_step() is not None:
            state, meta = mgr.restore(state)
            start = meta["step"] + 1
            print(f"[resume] step {meta['step']}")

    t0 = time.time()
    for i in range(start, args.steps):
        state, metrics = step_fn(state, pipe.get_batch(i))
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:5d} ce={float(metrics['ce']):.4f} "
                  f"lr={float(metrics['lr']):.2e} "
                  f"gnorm={float(metrics['grad_norm']):.2f} "
                  f"({(time.time() - t0) / max(1, i - start + 1):.2f}s/step)",
                  flush=True)
        if mgr and i > start and i % args.ckpt_every == 0:
            mgr.save(i, state, blocking=False)
    if mgr:
        mgr.wait()
        mgr.save(args.steps - 1, state)
    print("done")


if __name__ == "__main__":
    main()
