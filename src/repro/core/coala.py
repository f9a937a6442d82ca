"""COALA: inversion-free, regularized context-aware low-rank approximation.

Implements the paper's core results:

  * Proposition 1 — ``W' = U_r U_rᵀ W`` with U_r the top-r left singular
    vectors of ``W X``. No Gram matrix, no inversion, X arbitrary.
  * Proposition 2 — the same U_r from ``W Rᵀ`` where ``QR = Xᵀ`` (Algorithm 1).
  * Proposition 3 — regularized problem ≡ unregularized with X̃ = [X √μ I]
    (Algorithm 2), with the paper's Eq. (5) per-layer μ selection.
  * Proposition 4 — the (XXᵀ)^α family unifying PiSSA (α=0), COALA (α=1) and
    a robustified CorDA (α=2), used for adapter initialization.

Beyond-paper: a randomized (subspace-iteration) SVD path ``rsvd`` that only
computes the top-r subspace — O(m n r) matmul-only work, MXU-friendly on TPU —
while preserving the inversion-free structure.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import tsqr as tsqr_lib
from repro.core.precision import highest_precision


# ---------------------------------------------------------------------------
# SVD helpers
# ---------------------------------------------------------------------------

def _topk_left_singvecs(m: jax.Array, r: int) -> jax.Array:
    """Top-r left singular vectors of m via full SVD (paper-faithful path)."""
    u, _, _ = jnp.linalg.svd(m, full_matrices=False)
    return u[:, :r]


@partial(jax.jit, static_argnames=("r", "oversample", "power_iters"))
def rsvd_left_singvecs(m: jax.Array, r: int, *, oversample: int = 8,
                       power_iters: int = 2, seed: int = 0) -> jax.Array:
    """Randomized range finder for the top-r left subspace of ``m`` (beyond-paper).

    Halko–Martinsson–Tropp with QR-stabilized power iterations. All the work
    is matmul + thin QR — no Gram matrix of X is ever formed, so the
    inversion-free stability story is preserved (error controlled by
    ``power_iters``; see tests for the accuracy sweep).
    """
    mm, nn = m.shape
    l = min(r + oversample, nn)
    omega = jax.random.normal(jax.random.PRNGKey(seed), (nn, l), m.dtype)
    y = m @ omega                                  # (mm, l)
    q, _ = jnp.linalg.qr(y)
    for _ in range(power_iters):
        z, _ = jnp.linalg.qr(m.T @ q)
        q, _ = jnp.linalg.qr(m @ z)
    b = q.T @ m                                    # (l, nn)
    ub, _, _ = jnp.linalg.svd(b, full_matrices=False)
    u = q @ ub
    return u[:, :r]


# ---------------------------------------------------------------------------
# Algorithm 1 / 2 — the COALA solver
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CoalaResult:
    a: jax.Array          # (m, r)
    b: jax.Array          # (r, n)
    mu: float             # μ actually used
    r_factor: jax.Array   # the (possibly μ-augmented) R that was factored

    @property
    @highest_precision
    def w_approx(self) -> jax.Array:
        return self.a @ self.b


def r_from_x(x: jax.Array, chunk_tokens: int = 0) -> jax.Array:
    """R factor of qr(Xᵀ) for X (n, k); optionally via streaming TSQR chunks."""
    xt = x.T
    if chunk_tokens and xt.shape[0] > chunk_tokens:
        chunks = [xt[i:i + chunk_tokens] for i in range(0, xt.shape[0], chunk_tokens)]
        r = tsqr_lib.tsqr_sequential(chunks)
    else:
        r = tsqr_lib.qr_r(xt)
    return tsqr_lib.square_r(r)


@partial(jax.jit, static_argnames=("r",))
def _factor_from_r(w: jax.Array, r_factor: jax.Array, r: int) -> Tuple[jax.Array, jax.Array]:
    u_r = _topk_left_singvecs(w @ r_factor.T, r)
    return u_r, u_r.T @ w


@partial(jax.jit, static_argnames=("r", "oversample", "power_iters"))
def _factor_from_r_rsvd(w: jax.Array, r_factor: jax.Array, r: int,
                        oversample: int, power_iters: int) -> Tuple[jax.Array, jax.Array]:
    u_r = rsvd_left_singvecs(w @ r_factor.T, r,
                             oversample=oversample, power_iters=power_iters)
    return u_r, u_r.T @ w


@highest_precision
def coala_factors(
    w: jax.Array,
    x: Optional[jax.Array] = None,
    *,
    r_factor: Optional[jax.Array] = None,
    rank: int,
    mu: float = 0.0,
    lam: Optional[float] = None,
    use_rsvd: bool = False,
    rsvd_oversample: int = 8,
    rsvd_power_iters: int = 2,
    chunk_tokens: int = 0,
) -> CoalaResult:
    """COALA Algorithm 1/2. Provide either ``x`` (n, k) or a precomputed
    ``r_factor`` (n, n) from the calibration pipeline.

    mu/lam: explicit μ, or λ-driven Eq. (5) selection when ``lam`` is given
    (μ = λ · ||W₀X − WX||²_F / ||W₀ − W||²_F, computed from R only).
    """
    if (x is None) == (r_factor is None):
        raise ValueError("pass exactly one of x / r_factor")
    if r_factor is None:
        r_factor = r_from_x(x, chunk_tokens)
    r_factor = tsqr_lib.square_r(r_factor)

    solve = (partial(_factor_from_r_rsvd, oversample=rsvd_oversample,
                     power_iters=rsvd_power_iters)
             if use_rsvd else _factor_from_r)

    if lam is not None:
        a0, b0 = solve(w, r_factor, rank)
        mu = float(mu_from_lambda(w, a0 @ b0, r_factor, lam))
    if mu > 0.0:
        r_used = tsqr_lib.augment_r_with_mu(r_factor, mu)
    else:
        r_used = r_factor
    a, b = solve(w, r_used, rank)
    return CoalaResult(a=a, b=b, mu=float(mu), r_factor=r_used)


def coala_project(w, x=None, *, r_factor=None, rank: int, **kw) -> jax.Array:
    """Convenience: the rank-r approximation W' itself."""
    res = coala_factors(w, x, r_factor=r_factor, rank=rank, **kw)
    return res.w_approx


@jax.jit
def mu_from_lambda(w: jax.Array, w0: jax.Array, r_factor: jax.Array,
                   lam: float) -> jax.Array:
    """Paper Eq. (5): μ = λ · ||(W₀−W)X||²_F / ||W₀−W||²_F.

    Uses ||(W₀−W)X||_F = ||(W₀−W)Rᵀ||_F (Prop. 2 trick) so no X is needed.
    """
    diff = w0 - w
    num = jnp.sum((diff @ r_factor.T) ** 2)
    den = jnp.sum(diff ** 2)
    return lam * num / jnp.maximum(den, jnp.finfo(w.dtype).tiny)


# ---------------------------------------------------------------------------
# Proposition 4 — the α-family (adapter initialization)
# ---------------------------------------------------------------------------

def alpha_weight_factor(x_or_r: jax.Array, alpha: float, *, is_r: bool = False) -> jax.Array:
    """Return S_α with S_α S_αᵀ = (XXᵀ)^α, computed inversion-free.

    From the SVD of Xᵀ = Q Σ Vᵀ (or of R): (XXᵀ)^{α/2} = V Σ^α Vᵀ.
    α=0 → I (PiSSA), α=1 → (XXᵀ)^{1/2} (COALA), α=2 → XXᵀ (CorDA, robustified:
    formed from singular values of X, never from an explicit Gram matrix).
    """
    mat = x_or_r if is_r else x_or_r.T          # rows = tokens/R-rows, cols = n
    _, s, vt = jnp.linalg.svd(mat, full_matrices=False)
    n = mat.shape[1]
    s_full = jnp.zeros((n,), mat.dtype).at[: s.shape[0]].set(s)
    v = jnp.zeros((n, n), mat.dtype).at[:, : vt.shape[0]].set(vt.T)
    return (v * (s_full ** alpha)[None, :]) @ v.T


@highest_precision
def coala_alpha_factors(w: jax.Array, x: Optional[jax.Array] = None, *,
                        r_factor: Optional[jax.Array] = None,
                        rank: int, alpha: float = 1.0,
                        mu: float = 0.0) -> Tuple[jax.Array, jax.Array]:
    """Prop. 4 solution: W' = U_r U_rᵀ W with U_r from SVD(W (XXᵀ)^{α/2}).

    Returns (A, B) = (U_r, U_rᵀ W). For α=1 this coincides with Algorithm 1.
    """
    if (x is None) == (r_factor is None):
        raise ValueError("pass exactly one of x / r_factor")
    if mu < 0.0:
        raise ValueError(f"mu must be non-negative, got {mu}")
    if alpha == 1.0 and mu == 0.0:
        res = coala_factors(w, x, r_factor=r_factor, rank=rank)
        return res.a, res.b
    src = r_factor if r_factor is not None else x
    s_alpha = alpha_weight_factor(src, alpha, is_r=r_factor is not None)
    if mu > 0.0:
        # (XXᵀ)^α + μI via augmented-R of S_α (S_α is symmetric, rows = n)
        s_alpha = tsqr_lib.augment_r_with_mu(tsqr_lib.qr_r(s_alpha), mu).T
    u_r = _topk_left_singvecs(w @ s_alpha, rank)
    return u_r, u_r.T @ w


def balanced_split(a: jax.Array, b: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Rebalance (A, B) so both factors have comparable scale (adapter init:
    gradients are better conditioned when ||A col_i|| ≈ ||B row_i||).

    Per index i the scale ``sqrt(||B row_i|| / ||A col_i||)`` moves both
    norms to the geometric mean ``sqrt(||A col_i|| · ||B row_i||)`` for
    arbitrary (A, B) — e.g. baselines-produced or merged factors; when A's
    columns are orthonormal it reduces to the ``sqrt(||B row_i||)`` scale."""
    eps = jnp.finfo(b.dtype).eps
    bn = jnp.maximum(jnp.linalg.norm(b, axis=1), eps)    # (r,)
    an = jnp.maximum(jnp.linalg.norm(a, axis=0), eps)    # (r,)
    rn = jnp.sqrt(bn / an)
    return a * rn[None, :], b / rn[:, None]


# ---------------------------------------------------------------------------
# Reference (Eckart–Young–Mirsky) building block
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("rank",))
def eym_truncate(a: jax.Array, rank: int) -> jax.Array:
    """Best rank-r approximation of ``a`` in Frobenius norm (Theorem 3)."""
    u, s, vt = jnp.linalg.svd(a, full_matrices=False)
    return (u[:, :rank] * s[:rank][None, :]) @ vt[:rank, :]


@highest_precision
def weighted_error(w: jax.Array, w_approx: jax.Array, x: jax.Array) -> jax.Array:
    """||(W − W')X||_F — the objective of problem (3)."""
    return jnp.linalg.norm((w - w_approx) @ x)
