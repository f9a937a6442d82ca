"""Data-parallel Gram-free COALA calibration (paper §4.2, scaled out).

The calibration matrix ``X`` (features × tokens) for a production corpus
never fits on one device. The paper's answer — and this module's — is that
only the n×n ``R`` factor of ``Xᵀ`` is ever needed (Prop. 2), and R factors
compose by QR-stacking. So calibration shards the *token rows* over the
``data`` mesh axis:

  1. every shard streams its own activation rows into per-layer local R
     factors (the same TSQR fold as the single-device
     ``core.calibrate.Calibrator``, never materializing X);
  2. the per-shard R factors reduce with the butterfly
     ``core.tsqr.distributed_tsqr_r`` inside ``shard_map`` — log2(shards)
     ppermute+QR rounds, after which every device holds the identical full
     R. No Gram matrix, no gather, O(n²) per-device state.

Because R is unique for full-rank input under the non-negative-diagonal sign
convention, the combined R matches the single-device ``Calibrator`` output
for ANY shard count — entrywise within fp32 roundoff when X is
well-conditioned, and in general up to a left-orthogonal factor whose
entrywise footprint scales with cond(X) but under which COALA's weighted
projection (and the Gram form RᵀR) is exactly invariant. Shard-count
invariance is a testable contract (``tests/test_dist_calibrate.py``) in both
senses, not a hope. The Gram
path squares the condition number before it ever reduces; the QR path
reduces already-orthogonalized factors, which is why ill-conditioned
calibration survives sharding here and not in Gram-based baselines.

The batch goes onto the mesh sharded over ``data`` and the params
replicated, so each shard's sub-batch, a replica of the params and its R
factors sit on that shard's own device. The capture forward then runs as
one SPMD program per op over the whole mesh, compiled once for it (not
once per device), and every shard folds its own rows into its own R
inside ``shard_map``. Only step 2 touches the interconnect.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.calibrate import Calibrator
from repro.core.precision import highest_precision
from repro.core.tsqr import distributed_tsqr_r, qr_r, square_r, stack_qr
from repro.obs import trace


@functools.lru_cache(maxsize=None)
def _butterfly_reduce_fn(mesh, axis: str):
    """One jitted butterfly-reduce per (mesh, axis) — ``calibrate_sharded``
    calls it once per captured layer, and a fresh closure each time would
    re-trace and re-compile the identical (n, n) program per layer."""
    return jax.jit(jax.shard_map(
        lambda r: distributed_tsqr_r(r[0], axis),
        mesh=mesh, in_specs=P(axis, None, None), out_specs=P(),
        check_vma=False))


@functools.lru_cache(maxsize=None)
def _fold_fn(mesh, axis: str, max_tokens: int, first: bool):
    """Per-shard TSQR fold as one SPMD program: each shard folds its own
    rows of Xᵀ ``(k / S, n)``, in chunks of ``max_tokens``, into its own R
    (``(S, n, n)`` sharded over ``axis``). Cached per (mesh, axis,
    chunking, first fold), so each activation shape compiles once."""
    def fold(xt, r=None):
        r = None if first else r[0]
        for i in range(0, xt.shape[0], max_tokens):
            c = xt[i:i + max_tokens]
            r = qr_r(c) if r is None else stack_qr(r, c)
        return square_r(r)[None]

    in_specs = (P(axis, None),) + (() if first else (P(axis, None, None),))
    return jax.jit(jax.shard_map(fold, mesh=mesh, in_specs=in_specs,
                                 out_specs=P(axis, None, None),
                                 check_vma=False))


def combine_r_shards(r_shards, mesh, axis: str = "data") -> jax.Array:
    """Reduce per-shard R factors, an ``(S, n, n)`` array (or a list of S
    ``(n, n)`` factors), to one full R on-mesh.

    Runs the butterfly TSQR over ``axis`` inside ``shard_map``: each device
    holds its shard's R, pairs XOR-wise through ``ppermute``, and after
    log2(S) QR rounds every device holds the identical combined R (returned
    replicated). ``S`` must equal ``mesh.shape[axis]`` (power of two).
    """
    rs = jnp.stack(r_shards) if isinstance(r_shards, (list, tuple)) \
        else r_shards
    size = mesh.shape[axis]
    if rs.shape[0] != size:
        raise ValueError(
            f"{rs.shape[0]} R shards, mesh axis {axis!r} has size {size}")
    with trace.span("calib.butterfly_reduce", shards=size,
                    n=int(rs.shape[-1])):
        if size == 1:
            return square_r(qr_r(rs[0]))
        return _butterfly_reduce_fn(mesh, axis)(jax.device_put(
            rs, NamedSharding(mesh, P(axis, None, None))))


class _ShardCalibrator(Calibrator):
    """Capture sink that folds each record into per-shard R factors: the
    rows of a record are split over ``axis`` as the batch is, and shard
    ``s`` folds its rows into ``stacks[path][s]`` on its own device."""

    def __init__(self, mesh, axis: str):
        super().__init__()
        self.mesh, self.axis = mesh, axis
        self.n_shards = mesh.shape[axis]
        self.rows = NamedSharding(mesh, P(axis, None))
        self.stacks: Dict[str, jax.Array] = {}
        self.tokens: Dict[str, int] = {}

    def record(self, path: str, x: jax.Array, tokens: Optional[int] = None):
        """Fold ``x``'s rows; ``tokens`` counts the true ones where zero
        rows pad them (a routed expert's slots)."""
        n = x.shape[-1]
        flat = jnp.asarray(x, self.dtype).reshape(-1, n)
        k = flat.shape[0]
        pad = -k % self.n_shards
        if pad:
            # rows not split like the batch (a MoE expert's routed tokens):
            # zero rows leave RᵀR, and so R, unchanged
            flat = jnp.concatenate([flat, jnp.zeros((pad, n), flat.dtype)])
        flat = jax.device_put(flat, self.rows)
        k = k if tokens is None else tokens
        with trace.span("calib.record", path=path, tokens=k):
            prev = self.stacks.get(path)
            fold = _fold_fn(self.mesh, self.axis, self.max_tokens,
                            prev is None)
            self.stacks[path] = fold(flat) if prev is None \
                else fold(flat, prev)
            self.tokens[path] = self.tokens.get(path, 0) + k

    def record_experts(self, path, e_start, stacks, counts):
        """Each held expert's padded slots, folded per shard like any
        record (its zero rows change nothing), with its true count."""
        for j, pair in enumerate(stacks):
            for tap, stack in zip(("in", "hid"), pair):
                rows = stack.shape[1]
                got = np.clip(np.asarray(counts) - j * rows, 0, rows)
                for e in np.flatnonzero(got):
                    self.record(f"{path}/expert{e_start + e}/{tap}",
                                stack[e], tokens=int(got[e]))


@dataclasses.dataclass
class ShardedCalibration:
    """Result of ``calibrate_sharded`` — duck-types the ``Calibrator`` API
    that ``core.compress.compress_model`` consumes."""

    factors: Dict[str, jax.Array]
    tokens: Dict[str, int]
    n_shards: int
    # device that held each shard's R factors before the reduce
    shard_devices: Tuple[jax.Device, ...] = ()

    def r_factors(self) -> Dict[str, jax.Array]:
        return dict(self.factors)

    def tokens_seen(self) -> Dict[str, int]:
        return dict(self.tokens)


@highest_precision
def calibrate_sharded(model, params, batches: Iterable[dict], mesh, *,
                      axis: str = "data") -> ShardedCalibration:
    """Shard calibration rows over ``mesh`` axis ``axis``; butterfly-reduce
    per-shard R factors. Returns per-layer full R factors (replicated).

    Every batch's leading (row) dimension must divide by the axis size.
    """
    # the model's ops carry no sharding annotations: let the compiler
    # propagate the batch sharding (Explicit axes would demand them)
    mesh = jax.sharding.Mesh(mesh.devices, mesh.axis_names, axis_types=(
        jax.sharding.AxisType.Auto,) * len(mesh.axis_names))
    n = mesh.shape[axis]
    cal = _ShardCalibrator(mesh, axis)
    params = jax.device_put(params, NamedSharding(mesh, P()))
    rows = NamedSharding(mesh, P(axis))
    n_batches = 0
    for batch in batches:
        n_batches += 1
        b = jax.tree.leaves(batch)[0].shape[0]
        if b % n:
            raise ValueError(f"batch rows {b} not divisible by {n} shards")
        model.capture_forward(params, jax.device_put(batch, rows), cal)
    if n_batches == 0:
        raise ValueError("calibrate_sharded: no calibration batches")

    held: Dict[int, jax.Device] = {}
    for shard in next(iter(cal.stacks.values())).addressable_shards:
        held.setdefault(shard.index[0].start or 0, shard.device)
    factors = {path: combine_r_shards(stack, mesh, axis=axis)
               for path, stack in cal.stacks.items()}
    return ShardedCalibration(factors=factors, tokens=dict(cal.tokens),
                              n_shards=n,
                              shard_devices=tuple(held[i] for i in
                                                  sorted(held)))
