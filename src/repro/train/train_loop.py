"""Training step builder: CE loss, grad accumulation, clipping, AdamW,
mixed precision, optional cross-pod gradient compression.

``make_train_step`` returns a pure function suitable for ``jax.jit`` with
explicit in/out shardings (the launcher and the dry-run both use it).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import TrainConfig
from repro.models.common import ParallelCtx
from repro.train import grad_compress as gc
from repro.train.optimizer import adamw_init, adamw_update, clip_by_global_norm


def cast_for_compute(params, dtype):
    """bf16 compute copies of the fp32 master weights (matrices only —
    norm vectors stay fp32 for stability)."""
    def cast(x):
        if x.ndim >= 2 and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x
    return jax.tree.map(cast, params)


def make_train_state(model, tcfg: TrainConfig, key, param_dtype=jnp.float32):
    params = model.init(key, dtype=param_dtype)
    state = {"params": params, "opt": adamw_init(params)}
    if tcfg.grad_compress_pods:
        state["err"] = None  # filled by the launcher once n_pods is known
    return state


def make_train_step(model, tcfg: TrainConfig, ctx: ParallelCtx,
                    mesh=None, batch_leaf_spec=None, compute_specs=None):
    """``compute_specs``: optional PartitionSpec tree for a bf16 TP-sharded
    compute copy of the weights (ZeRO-1 "hoisted cast"). When given, the
    fp32 master stays FSDP-sharded and is all-gathered ONCE per step in
    bf16 (outside the microbatch loop); per-microbatch gradients are taken
    w.r.t. the compute copy and accumulated in fp32 — one bf16 all-gather +
    one fp32 reduce-scatter per step instead of per microbatch."""
    compute_dtype = jnp.dtype(tcfg.compute_dtype)

    def loss_and_grad(params, batch):
        def loss_fn(p):
            pc = cast_for_compute(p, compute_dtype)
            return model.loss(pc, batch, ctx=ctx, remat=tcfg.remat,
                              compute_dtype=compute_dtype)

        if tcfg.microbatches > 1:
            tokens = batch["tokens"]
            b = tokens.shape[0]
            mb = tcfg.microbatches
            assert b % mb == 0, (b, mb)

            def split(x):
                return x.reshape(mb, b // mb, *x.shape[1:])

            mbatch = {k: split(v) for k, v in batch.items()}

            def mb_loss(p, mbb):
                pc = cast_for_compute(p, compute_dtype)
                return model.loss(pc, mbb, ctx=ctx, remat=tcfg.remat,
                                  compute_dtype=compute_dtype)

            def body(carry, mbb):
                acc_g, acc_l, acc_a = carry
                (loss, metrics), grads = jax.value_and_grad(
                    mb_loss, has_aux=True)(params, mbb)
                acc_g = jax.tree.map(jnp.add, acc_g, grads)
                return (acc_g, acc_l + metrics["ce"], acc_a + metrics["aux"]), None

            zero_g = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                                  params)
            (g, ce, aux), _ = jax.lax.scan(
                body, (zero_g, jnp.zeros((), jnp.float32),
                       jnp.zeros((), jnp.float32)), mbatch)
            g = jax.tree.map(lambda x: x / mb, g)
            metrics = {"ce": ce / mb, "aux": aux / mb}
            return (metrics["ce"] + metrics["aux"], metrics), g

        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        return (loss, metrics), grads

    use_compress = (tcfg.grad_compress_pods and mesh is not None
                    and "pod" in mesh.axis_names)
    if use_compress:
        # the loss runs under vmap over the explicit pod axis (see
        # grad_compress.py), with the same layout hints and expert-parallel
        # shard_map as the uncompressed step
        def pod_loss_and_grad(params, batch):
            def loss_fn(p):
                pc = cast_for_compute(p, compute_dtype)
                return model.loss(pc, batch, ctx=ctx, remat=tcfg.remat,
                                  compute_dtype=compute_dtype)
            return jax.value_and_grad(loss_fn, has_aux=True)(params)

        def batch_spec_fn(leaf):
            return P("pod", *([None] * (leaf.ndim - 1)))
        compressed = gc.make_compressed_grads_fn(pod_loss_and_grad, mesh,
                                                 batch_spec_fn)

    def hoisted_loss_and_grad(params, batch):
        from jax.sharding import NamedSharding
        pc = cast_for_compute(params, compute_dtype)
        pc = jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, s)),
            pc, compute_specs, is_leaf=lambda x: hasattr(x, "shape"))

        def mb_loss(p, mbb):
            return model.loss(p, mbb, ctx=ctx, remat=tcfg.remat,
                              compute_dtype=compute_dtype)

        mb = tcfg.microbatches
        if mb > 1:
            b = batch["tokens"].shape[0]
            assert b % mb == 0, (b, mb)
            mbatch = {k: v.reshape(mb, b // mb, *v.shape[1:])
                      for k, v in batch.items()}

            def body(carry, mbb):
                acc_g, ce, aux = carry
                (_, metrics), g = jax.value_and_grad(
                    mb_loss, has_aux=True)(pc, mbb)
                acc_g = jax.tree.map(
                    lambda a, x: a + x.astype(jnp.float32), acc_g, g)
                return (acc_g, ce + metrics["ce"], aux + metrics["aux"]), None

            zero_g = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                                  pc)
            (g, ce, aux), _ = jax.lax.scan(
                body, (zero_g, jnp.zeros((), jnp.float32),
                       jnp.zeros((), jnp.float32)), mbatch)
            g = jax.tree.map(lambda x: x / mb, g)
            metrics = {"ce": ce / mb, "aux": aux / mb}
            return (metrics["ce"] + metrics["aux"], metrics), g
        (loss, metrics), g = jax.value_and_grad(mb_loss, has_aux=True)(
            pc, batch)
        g = jax.tree.map(lambda x: x.astype(jnp.float32), g)
        return (loss, metrics), g

    def train_step(state, batch):
        params = state["params"]
        if use_compress:
            loss, metrics, grads, new_err = compressed(params, batch,
                                                       state["err"])
        elif compute_specs is not None and mesh is not None:
            (loss, metrics), grads = hoisted_loss_and_grad(params, batch)
            new_err = state.get("err")
        else:
            (loss, metrics), grads = loss_and_grad(params, batch)
            new_err = state.get("err")
        grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
        new_params, new_opt, lr = adamw_update(tcfg, params, grads,
                                               state["opt"])
        new_state = {"params": new_params, "opt": new_opt}
        if "err" in state:
            new_state["err"] = new_err
        out_metrics = dict(metrics)
        out_metrics.update(loss=loss, grad_norm=gnorm, lr=lr)
        return new_state, out_metrics

    return train_step


# train-state spec assembly lives in repro.dist.sharding.train_state_specs
# (fsdp / zero1 / zero1h strategies) — the launchers and dry-run use that.
