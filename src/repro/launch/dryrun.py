import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks device count on first init.

"""Multi-pod dry-run: lower + compile every (arch × shape) on the production
meshes and record memory/cost/collective analysis for §Dry-run / §Roofline.

  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm_135m \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all        # resumable sweep

Results: experiments/dryrun/<arch>__<shape>__<mesh>[__variant].json
"""
import argparse
import json
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compile_cache
from repro.config import SHAPES, TrainConfig
from repro.configs import ARCH_IDS, get_config
from repro.dist.sharding import (batch_axes_of, batch_specs, cache_specs,
                                 param_specs, to_named, train_state_specs)
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.models.common import ParallelCtx
from repro.models.transformer import period_specs
from repro.roofline import analysis as roofline
from repro.roofline import jaxpr_cost
from repro.train.train_loop import make_train_step, make_train_state

ASSIGNED = [a for a in ARCH_IDS if a not in ("llama3_1b", "mistral_7b")]

# long_500k needs sub-quadratic attention: run only for SSM/hybrid/local-attn
LONG_OK = {"xlstm_1_3b", "jamba_v0_1_52b", "gemma2_27b"}


def cells():
    for arch in ASSIGNED:
        for shape in ("train_4k", "prefill_32k", "decode_32k", "long_500k"):
            if shape == "long_500k" and arch not in LONG_OK:
                continue
            yield arch, shape


def make_ctx(cfg, mesh, *, mlstm_chunkwise: bool = False) -> ParallelCtx:
    return ParallelCtx(
        mesh=mesh, batch_axes=batch_axes_of(mesh),
        shard_map_moe=cfg.uses_moe,
        dense_attn_max_seq=2048, attn_chunk_q=2048, attn_chunk_kv=1024,
        mlstm_chunkwise=mlstm_chunkwise)


def auto_microbatches(cfg, shape_cfg, mesh) -> int:
    """Pick grad-accum so the scan-saved residual stream fits ~2GB/device."""
    n_shards = 1
    for a in batch_axes_of(mesh):
        n_shards *= mesh.shape[a]
    b_loc = max(1, shape_cfg.global_batch // n_shards)
    if cfg.family == "encdec":
        n_rep = cfg.n_layers + (cfg.n_enc_layers or cfg.n_layers)
    else:
        _, _, n_rep = period_specs(cfg)
    carry_bytes = b_loc * shape_cfg.seq_len * cfg.d_model * 2 * n_rep
    # chunked-attention backward keeps ~one layer's score blocks resident:
    # b x kv_heads_local x T^2 x 4B (heads shard over model only if divisible)
    s_model = mesh.shape.get("model", 1)
    h_loc = (cfg.n_kv_heads // s_model if cfg.n_kv_heads % s_model == 0
             else cfg.n_kv_heads)
    att_bytes = (b_loc * h_loc * shape_cfg.seq_len * shape_cfg.seq_len * 4
                 if cfg.family != "ssm" else 0)
    target = 2 << 30
    mb = 1
    while max(carry_bytes, att_bytes) / mb > target and mb < b_loc:
        mb *= 2
    return mb


def tokens_sds(cfg, shape_cfg, kind: str) -> Dict[str, jax.ShapeDtypeStruct]:
    b, t = shape_cfg.global_batch, shape_cfg.seq_len
    out = {}
    if kind == "decode":
        out["tokens"] = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        return out
    t_text = t - (cfg.n_vision_tokens if cfg.family == "vlm" else 0)
    out["tokens"] = jax.ShapeDtypeStruct((b, t_text), jnp.int32)
    if cfg.family == "encdec":
        out["frames"] = jax.ShapeDtypeStruct(
            (b, cfg.n_audio_frames, cfg.d_model), jnp.bfloat16)
    if cfg.family == "vlm":
        out["vision_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.n_vision_tokens, cfg.d_model), jnp.bfloat16)
    return out


def abstract_factorize(params_sds, cfg, ratio: float):
    """COALA-compressed parameter skeleton: replace large dense linears with
    (b_t, a_t) factor pairs at the given kept-parameter ratio."""
    from repro.core.compress import compressible, rank_for_ratio_dims
    import jax.tree_util as jtu

    def walk(tree, path=()):
        if isinstance(tree, dict):
            if "w" in tree and compressible(path, tree["w"].shape, cfg):
                w = tree["w"]
                d_in, d_out = w.shape[-2], w.shape[-1]
                r = rank_for_ratio_dims(d_in, d_out, ratio)
                lead = w.shape[:-2]        # stacked-layer dim for scanned blocks
                return {"b_t": jax.ShapeDtypeStruct(lead + (d_in, r), w.dtype),
                        "a_t": jax.ShapeDtypeStruct(lead + (r, d_out), w.dtype)}
            return {k: walk(v, path + (k,)) for k, v in tree.items()}
        if isinstance(tree, list):
            return [walk(v, path + (str(i),)) for i, v in enumerate(tree)]
        return tree

    return walk(params_sds)


def lower_cell(arch: str, shape: str, mesh_name: str, *,
               compress_ratio: float = 0.0, grad_compress: bool = False,
               zero: str = "fsdp", remat: str = "full",
               mlstm_chunkwise: bool = False) -> dict:
    cfg = get_config(arch)
    shape_cfg = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    ctx = make_ctx(cfg, mesh, mlstm_chunkwise=mlstm_chunkwise)
    model = build_model(cfg)
    t0 = time.time()

    if shape_cfg.kind == "train":
        mb = auto_microbatches(cfg, shape_cfg, mesh)
        tcfg = TrainConfig(microbatches=mb, remat=remat,
                           grad_compress_pods=grad_compress)
        state_sds = jax.eval_shape(
            lambda k: make_train_state(model, tcfg, k), jax.random.PRNGKey(0))
        if grad_compress and "pod" in mesh.axis_names:
            from repro.train import grad_compress as gc
            state_sds["err"] = jax.eval_shape(
                lambda p: gc.init_error_state(p, mesh.shape["pod"]),
                state_sds["params"])
        batch_sds = tokens_sds(cfg, shape_cfg, "train")
        if zero == "zero1h":
            # fp32 master fully sharded; bf16 TP compute copy hoisted per step
            sspecs = train_state_specs(cfg, state_sds, mesh, strategy="fsdp")
            cspecs = param_specs(cfg, state_sds["params"], mesh, mode="infer")
            step = make_train_step(model, tcfg, ctx, mesh=mesh,
                                   compute_specs=cspecs)
        else:
            sspecs = train_state_specs(cfg, state_sds, mesh, strategy=zero)
            step = make_train_step(model, tcfg, ctx, mesh=mesh)
        bspecs = batch_specs(cfg, batch_sds, mesh)
        jitted = jax.jit(step,
                         in_shardings=(to_named(sspecs, mesh),
                                       to_named(bspecs, mesh)),
                         out_shardings=(to_named(sspecs, mesh), None),
                         donate_argnums=0)
        lowered = jitted.lower(state_sds, batch_sds)
        jcost = jaxpr_cost.trace_cost(step, state_sds, batch_sds)
        params_for_flops = state_sds["params"]
        meta = {"microbatches": mb, "remat": remat, "zero": zero,
                "grad_compress": grad_compress}
    else:
        params_sds = jax.eval_shape(
            lambda k: model.init(k, dtype=jnp.bfloat16), jax.random.PRNGKey(0))
        if compress_ratio > 0:
            params_sds = abstract_factorize(params_sds, cfg, compress_ratio)
        cache_sds = jax.eval_shape(
            lambda: model.init_cache(shape_cfg.global_batch,
                                     shape_cfg.seq_len, dtype=jnp.bfloat16))
        pspecs = param_specs(cfg, params_sds, mesh, mode="infer")
        cspecs = cache_specs(cfg, cache_sds, mesh)
        batch_sds = tokens_sds(cfg, shape_cfg, shape_cfg.kind)
        bspecs = batch_specs(cfg, batch_sds, mesh)

        if shape_cfg.kind == "prefill":
            def fn(params, batch, cache):
                kw = {k: v for k, v in batch.items() if k != "tokens"}
                if cfg.family == "encdec":
                    return model.prefill(params, batch["tokens"], cache,
                                         ctx=ctx, frames=kw["frames"])
                if cfg.family == "vlm":
                    return model.prefill(params, batch["tokens"], cache,
                                         ctx=ctx,
                                         vision_embeds=kw["vision_embeds"])
                return model.prefill(params, batch["tokens"], cache, ctx=ctx)
            jitted = jax.jit(
                fn,
                in_shardings=(to_named(pspecs, mesh), to_named(bspecs, mesh),
                              to_named(cspecs, mesh)),
                out_shardings=(None, to_named(cspecs, mesh)),
                donate_argnums=2)
            lowered = jitted.lower(params_sds, batch_sds, cache_sds)
            jcost = jaxpr_cost.trace_cost(fn, params_sds, batch_sds, cache_sds)
        else:
            def fn(params, tokens, cache, pos):
                return model.decode_step(params, tokens, cache, pos, ctx=ctx)
            pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
            jitted = jax.jit(
                fn,
                in_shardings=(to_named(pspecs, mesh),
                              NamedSharding(mesh, P()),
                              to_named(cspecs, mesh),
                              NamedSharding(mesh, P())),
                out_shardings=(None, to_named(cspecs, mesh)),
                donate_argnums=2)
            lowered = jitted.lower(params_sds, batch_sds["tokens"],
                                   cache_sds, pos_sds)
            jcost = jaxpr_cost.trace_cost(fn, params_sds, batch_sds["tokens"],
                                          cache_sds, pos_sds)
        params_for_flops = params_sds
        meta = {"compress_ratio": compress_ratio}

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mf = roofline.model_flops(cfg, params_for_flops, shape_cfg)
    rf = roofline.analyze(compiled, arch=arch, shape=shape,
                          mesh_name=mesh_name,
                          n_devices=mesh.devices.size,
                          model_flops_global=mf, jaxpr_cost=jcost)
    out = rf.to_json()
    out.update(status="ok", t_lower_s=round(t_lower, 1),
               t_compile_s=round(t_compile, 1), meta=meta,
               param_count=roofline.count_params(params_for_flops))
    return out


def run_cell(arch, shape, mesh_name, out_dir, *, force=False,
             compress_ratio=0.0, grad_compress=False, tag="",
             zero="fsdp", remat="full", mlstm_chunkwise=False):
    os.makedirs(out_dir, exist_ok=True)
    name = f"{arch}__{shape}__{mesh_name}" + (f"__{tag}" if tag else "")
    path = os.path.join(out_dir, name + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            prev = json.load(f)
        if prev.get("status") == "ok":
            print(f"[skip] {name} (cached)")
            return prev
    print(f"[run ] {name} ...", flush=True)
    try:
        out = lower_cell(arch, shape, mesh_name,
                         compress_ratio=compress_ratio,
                         grad_compress=grad_compress, zero=zero, remat=remat,
                         mlstm_chunkwise=mlstm_chunkwise)
    except Exception as e:  # record the failure — it is a bug to fix
        out = {"status": "error", "arch": arch, "shape": shape,
               "mesh": mesh_name, "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
        print(f"[FAIL] {name}: {out['error']}", flush=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1, default=float)
    if out.get("status") == "ok":
        print(f"[ok  ] {name}: dom={out['dominant']} "
              f"tc={out['t_compute']:.4f}s tm={out['t_memory']:.4f}s "
              f"tl={out['t_collective']:.4f}s "
              f"frac={out['roofline_fraction']:.3f} "
              f"(compile {out['t_compile_s']}s)", flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out-dir", default="experiments/dryrun")
    ap.add_argument("--compress-ratio", type=float, default=0.0)
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--zero", default="fsdp", choices=["fsdp", "zero1", "zero1h"])
    ap.add_argument("--remat", default="full", choices=["none", "dots", "full"])
    ap.add_argument("--mlstm-chunkwise", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    compile_cache.enable()

    if args.all:
        fails = 0
        for mesh_name in ("single", "multi"):
            for arch, shape in cells():
                out = run_cell(arch, shape, mesh_name, args.out_dir,
                               force=args.force)
                fails += out.get("status") != "ok"
        print(f"\nsweep done, failures: {fails}")
        raise SystemExit(1 if fails else 0)

    assert args.arch and args.shape, "--arch/--shape required without --all"
    out = run_cell(args.arch, args.shape, args.mesh, args.out_dir,
                   force=args.force, compress_ratio=args.compress_ratio,
                   grad_compress=args.grad_compress, tag=args.tag,
                   zero=args.zero, remat=args.remat,
                   mlstm_chunkwise=args.mlstm_chunkwise)
    raise SystemExit(0 if out.get("status") == "ok" else 1)


if __name__ == "__main__":
    main()
