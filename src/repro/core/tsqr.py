"""TSQR: Tall-Skinny QR for calibration matrices that never fit in memory.

The paper (§4.2) preprocesses the activation matrix ``X ∈ R^{n×k}`` (k = tokens,
can be millions) by a QR decomposition of ``Xᵀ``; only the ``R`` factor (n×n)
is needed downstream (Prop. 2). For large k we use the TSQR scheme of
Demmel et al. [11]:

  * ``tsqr_sequential`` — streaming: fold chunks into a running R (the paper's
    ``[R; X_iᵀ] → QR`` recurrence). O(n²) state, one pass over the data.
  * ``tsqr_tree`` — binary reduction tree over chunks (the paper's multi-GPU
    diagram).
  * ``distributed_tsqr_r`` — the TPU-native adaptation: a butterfly
    (XOR-pairing) reduction over a mesh axis inside ``shard_map``, built on
    ``lax.ppermute``. After log2(axis) rounds every device holds the SAME
    full R — an "all-reduce" in QR-land. This is the paper's tree mapped
    onto ICI collectives.

All functions return R with a sign convention (non-negative diagonal) so that
R is unique and comparable across strategies in tests.
"""
from __future__ import annotations

import math
from typing import Iterable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.obs import trace


def _fix_sign(r: jax.Array) -> jax.Array:
    """Flip row signs so diag(R) >= 0 (makes R unique for full-rank input)."""
    d = jnp.diagonal(r)
    s = jnp.where(d < 0, -1.0, 1.0).astype(r.dtype)
    return r * s[:, None]


def qr_r(xt: jax.Array, fix_sign: bool = True) -> jax.Array:
    """R factor of the (reduced) QR of ``xt`` (rows = tokens, cols = n)."""
    r = jnp.linalg.qr(xt, mode="r")
    return _fix_sign(r) if fix_sign else r


def stack_qr(r_top: jax.Array, r_bot: jax.Array) -> jax.Array:
    """R factor of qr([R_top; R_bot]) — the TSQR combine step."""
    return qr_r(jnp.concatenate([r_top, r_bot], axis=0))


def tsqr_sequential(chunks: Iterable[jax.Array]) -> jax.Array:
    """Streaming TSQR: fold token-chunks (each (k_i, n) rows of Xᵀ)."""
    r: Optional[jax.Array] = None
    for c in chunks:
        if c.ndim != 2:
            raise ValueError(f"chunk must be 2-D (tokens, features), got {c.shape}")
        r = qr_r(c) if r is None else stack_qr(r, c)
    if r is None:
        raise ValueError("tsqr_sequential: no chunks")
    return r


def tsqr_tree(chunks: Sequence[jax.Array]) -> jax.Array:
    """Binary-tree TSQR (paper Fig. in §4.2): pairwise combine until one R."""
    rs = [qr_r(c) for c in chunks]
    while len(rs) > 1:
        nxt = []
        for i in range(0, len(rs) - 1, 2):
            nxt.append(stack_qr(rs[i], rs[i + 1]))
        if len(rs) % 2 == 1:
            nxt.append(rs[-1])
        rs = nxt
    return rs[0]


def calib_fold_first(chunk: jax.Array) -> jax.Array:
    """R of a stream's first chunk, square (jitted, the program
    ``calib_fold_first``)."""
    return square_r(qr_r(chunk))


def calib_fold(r: jax.Array, chunk: jax.Array) -> jax.Array:
    """Fold a chunk into a stream's R (jitted, the program ``calib_fold``)."""
    return stack_qr(r, chunk)


def calib_fold_group(rs: Tuple[jax.Array, ...], chunks: jax.Array
                     ) -> Tuple[jax.Array, ...]:
    """Fold each of E chunks into its own stream's R: ``rs`` E square
    (n, n) R factors (zero for a stream with no rows yet: zero rows leave
    RᵀR, and so R, unchanged), ``chunks`` (E, rows, n) zero-padded. Jitted
    per (E, rows, n), the program ``calib_fold_group``: one program folds
    every expert a MoE layer holds."""
    return tuple(jax.vmap(stack_qr)(jnp.stack(rs), chunks))


class RStreamer:
    """Stateful streaming R accumulator used by the calibration pipeline.

    Never materializes X: ``update`` consumes a (tokens, n) activation chunk,
    ``finish`` returns the final R (optionally μ-augmented, Prop. 3). R is
    kept square, zero-padded where the first chunk has fewer rows than
    width, so every later fold of a stream is one program.
    """

    def __init__(self, n: int, dtype=jnp.float32):
        self.n = n
        self.dtype = dtype
        self._r: Optional[jax.Array] = None
        self.tokens_seen = 0
        self._update = jax.jit(calib_fold)
        self._first = jax.jit(calib_fold_first)

    def update(self, chunk: jax.Array) -> None:
        chunk = chunk.reshape(-1, self.n).astype(self.dtype)
        rows = int(chunk.shape[0])
        self.tokens_seen += rows
        with trace.span("calib.fold", rows=rows, n=self.n):
            self._r = (self._first(chunk) if self._r is None
                       else self._update(self._r, chunk))

    @property
    def state(self) -> Tuple[Optional[jax.Array], int]:
        """``(R or None, tokens_seen)``: what the next fold starts from."""
        return self._r, self.tokens_seen

    @state.setter
    def state(self, state: Tuple[Optional[jax.Array], int]) -> None:
        self._r, self.tokens_seen = state

    @property
    def r(self) -> jax.Array:
        if self._r is None:
            raise ValueError("RStreamer: no data seen")
        return self._r

    def finish(self, mu: float = 0.0) -> jax.Array:
        r = self.r
        if mu > 0.0:
            r = augment_r_with_mu(r, mu)
        return square_r(r)


def square_r(r: jax.Array) -> jax.Array:
    """Pad/keep R to a square (n, n) upper-triangular matrix."""
    k, n = r.shape
    if k == n:
        return r
    if k > n:  # cannot happen for reduced QR, but be safe
        return qr_r(r)
    return jnp.zeros((n, n), r.dtype).at[:k, :].set(r)


def augment_r_with_mu(r: jax.Array, mu: float) -> jax.Array:
    """R of the μ-augmented matrix X̃ = [X  √μ·I] (Prop. 3): qr([R; √μ I])."""
    n = r.shape[-1]
    eye = jnp.sqrt(jnp.asarray(mu, r.dtype)) * jnp.eye(n, dtype=r.dtype)
    return stack_qr(square_r(r), eye)


# ---------------------------------------------------------------------------
# Distributed TSQR over a mesh axis (shard_map body)
# ---------------------------------------------------------------------------

def distributed_tsqr_r(xt_local: jax.Array, axis_name: str) -> jax.Array:
    """Butterfly TSQR over mesh axis ``axis_name`` (call inside shard_map).

    xt_local: (k_local, n) local rows of Xᵀ. Returns the full R (replicated:
    every device along the axis computes the identical matrix).
    """
    size = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    r = qr_r(xt_local)
    r = square_r(r)  # keep (n, n) so every round has a static shape
    rounds = int(math.log2(size))
    if 2 ** rounds != size:
        raise ValueError(f"axis size {size} must be a power of two for butterfly TSQR")
    for s in range(rounds):
        shift = 1 << s
        perm = [(i, i ^ shift) for i in range(size)]
        other = jax.lax.ppermute(r, axis_name, perm)
        partner = me ^ shift
        # Deterministic stacking order (lower device id on top) so both sides
        # of the pair compute the *same* R and the result stays replicated.
        top = jnp.where(me < partner, 0, 1)
        stacked = jnp.where(top == 0,
                            jnp.concatenate([r, other], axis=0),
                            jnp.concatenate([other, r], axis=0))
        r = qr_r(stacked)
    return r


def gram_chunked(chunks: Iterable[jax.Array]) -> jax.Array:
    """Baseline Gram accumulation  XXᵀ = Σ XᵢXᵢᵀ  (the numerically risky path
    the paper compares against; kept for the SVD-LLM baselines)."""
    g: Optional[jax.Array] = None
    for c in chunks:  # c: (tokens, n) rows of Xᵀ  -> contributes cᵀc
        contrib = c.T @ c
        g = contrib if g is None else g + contrib
    if g is None:
        raise ValueError("gram_chunked: no chunks")
    return g
