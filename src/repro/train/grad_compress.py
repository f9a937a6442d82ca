"""Two-level gradient reduction for multi-pod training.

Within a pod the data axis reduces gradients in full precision — ICI is fast
(GSPMD reduce-scatter from FSDP). ACROSS pods (DCN / optical links, ~10x
slower) we reduce int8-quantized gradients with **error feedback**:

    q_t  = quant(g_t + e_{t-1})
    ĝ_t  = mean_pods(dequant(q_t))
    e_t  = (g_t + e_{t-1}) - dequant(q_t)       # residual kept on the pod

Error feedback telescopes the quantization bias across steps, which is what
keeps convergence intact (EF-SGD, Karimireddy et al. 2019). Quantization is
per-block(128) symmetric int8 with an fp32 scale — ~4x fewer cross-pod bytes.

Structure (two phases, keeping the model OUT of any manual region):

  1. the batch reshapes to a leading pod axis ``(n_pods, B/n_pods, ...)``
     sharded ``P('pod', ...)`` and the loss+grad runs under ``jax.vmap``
     over that axis — per-pod gradients come out with an explicit leading
     pod dim instead of being fused into backward's pod reduction, while
     the data/model axes stay ordinary GSPMD code;
  2. ONLY the quantize → psum → dequantize reduction runs inside a
     ``shard_map`` that is manual over the ``pod`` axis. Its body is
     elementwise math plus one ``psum``; the model itself stays ordinary
     GSPMD code over (data, model), outside any manual region.

The error state carries an explicit leading pod axis (spec ``P('pod', ...)``)
so each pod's residual survives round-trips through the global value.
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

BLOCK = 128


def _quantize(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-block symmetric int8. Returns (int8 payload, fp32 scales)."""
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % BLOCK
    if pad:
        flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, BLOCK)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize(q: jax.Array, scale: jax.Array, shape) -> jax.Array:
    flat = (q.astype(jnp.float32) * scale).reshape(-1)
    n = 1
    for s in shape:
        n *= s
    return flat[:n].reshape(shape)


def quantized_mean_leaf(g: jax.Array, err: jax.Array, axis_name: str):
    """One leaf: error-feedback int8 psum-mean over ``axis_name``."""
    target = g.astype(jnp.float32) + err
    q, scale = _quantize(target)
    local_deq = _dequantize(q, scale, g.shape)
    new_err = target - local_deq
    size = jax.lax.axis_size(axis_name)
    g_hat = jax.lax.psum(local_deq, axis_name) / size
    return g_hat.astype(g.dtype), new_err


def init_error_state(params, n_pods: int):
    """fp32 residuals with an explicit leading pod axis."""
    return jax.tree.map(
        lambda x: jnp.zeros((n_pods,) + x.shape, jnp.float32), params)


def error_state_specs(params):
    return jax.tree.map(lambda _: P("pod"), params)


def make_compressed_grads_fn(loss_and_grad_fn: Callable, mesh,
                             batch_spec_fn: Callable):
    """Wraps ``loss_and_grad_fn(params, batch) -> ((loss, metrics), grads)``
    into a pod-manual region with int8+EF cross-pod gradient reduction.

    Returns ``f(params, batch, err) -> (loss, metrics, grads, new_err)``.
    """

    from jax.sharding import NamedSharding

    n_pods = mesh.shape["pod"]

    def wrapped(params, batch, err):
        # ---- phase 1: per-pod grads via vmap over an explicit pod axis ----
        def to_pod_major(x, flat_spec):
            y = x.reshape((n_pods, x.shape[0] // n_pods) + x.shape[1:])
            spec = P("pod", None, *tuple(flat_spec)[1:])
            return jax.lax.with_sharding_constraint(
                y, NamedSharding(mesh, spec))

        pbatch = jax.tree.map(
            lambda x: to_pod_major(x, batch_spec_fn(x)), batch)
        (loss_p, metrics_p), grads_p = jax.vmap(
            loss_and_grad_fn, in_axes=(None, 0))(params, pbatch)

        # ---- phase 2: int8+EF reduction, manual over pod only ------------
        flat_g, gdef = jax.tree.flatten(grads_p)
        flat_e = gdef.flatten_up_to(err)
        ng = len(flat_g)

        def body(*args):
            gs, es = args[:ng], args[ng:]
            outs = [quantized_mean_leaf(g[0], e[0], "pod")
                    for g, e in zip(gs, es)]
            return ([o[0] for o in outs], [o[1][None] for o in outs])

        in_specs = (tuple(P("pod") for _ in flat_g)
                    + tuple(P("pod") for _ in flat_e))
        out_specs = ([P() for _ in flat_g], [P("pod") for _ in flat_g])
        new_g_flat, new_e_flat = jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=tuple(out_specs),
            check_vma=False, axis_names={"pod"})(*flat_g, *flat_e)
        grads = gdef.unflatten(new_g_flat)
        new_err = gdef.unflatten(new_e_flat)

        loss = jnp.mean(loss_p)
        metrics = jax.tree.map(lambda m: jnp.mean(m, axis=0), metrics_p)
        return (loss, metrics, grads, new_err)

    return wrapped


def simulate_roundtrip(g: jax.Array) -> jax.Array:
    """Single-device test helper: quantize→dequantize without a mesh."""
    q, s = _quantize(g)
    return _dequantize(q, s, g.shape).astype(g.dtype)
