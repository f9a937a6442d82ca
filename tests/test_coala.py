"""Numerics tests for the COALA core (Props 1-4, Algorithms 1-2, Eq. 5)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    balanced_split, coala_factors, coala_project, coala_alpha_factors,
    eym_truncate, r_from_x, rsvd_left_singvecs, weighted_error,
)
from repro.core import baselines, theory
from repro.core.coala import mu_from_lambda


def _rand(m, n, key, scale=1.0):
    return scale * jax.random.normal(jax.random.PRNGKey(key), (m, n), jnp.float32)


class TestProposition1:
    """W' = U_r U_rᵀ W attains the optimal weighted error."""

    @pytest.mark.parametrize("m,n,k,r", [(24, 16, 40, 4), (16, 24, 64, 6),
                                         (32, 32, 8, 3)])  # incl. k < n (limited data)
    def test_attains_optimum(self, m, n, k, r):
        w, x = _rand(m, n, 0), _rand(n, k, 1)
        w_apx = coala_project(w, x, rank=r)
        err = weighted_error(w, w_apx, x)
        opt = theory.optimal_weighted_error(w, x, r)
        np.testing.assert_allclose(err, opt, rtol=1e-4, atol=1e-5)

    def test_rank_constraint(self):
        w, x = _rand(20, 16, 0), _rand(16, 50, 1)
        res = coala_factors(w, x, rank=5)
        assert res.a.shape == (20, 5) and res.b.shape == (5, 16)
        assert np.linalg.matrix_rank(np.asarray(res.w_approx), tol=1e-4) <= 5

    def test_beats_or_matches_baselines(self):
        w, x = _rand(24, 16, 2), _rand(16, 48, 3)
        r = 4
        coala_err = weighted_error(w, coala_project(w, x, rank=r), x)
        for a, b in [baselines.plain_svd(w, r), baselines.asvd(w, x, r)]:
            assert coala_err <= weighted_error(w, a @ b, x) + 1e-5


class TestProposition2:
    """QR preprocessing gives the identical solution."""

    def test_r_path_equals_x_path(self):
        w, x = _rand(20, 12, 4), _rand(12, 300, 5)
        r_factor = r_from_x(x)
        direct = coala_project(w, x, rank=4)
        via_r = coala_project(w, r_factor=r_factor, rank=4)
        # solutions may differ only in the null space when degenerate;
        # here X is full row rank so W' is unique in the row space metric
        np.testing.assert_allclose(np.asarray(direct), np.asarray(via_r),
                                   rtol=1e-4, atol=1e-5)

    def test_chunked_tsqr_matches(self):
        w, x = _rand(20, 12, 6), _rand(12, 1000, 7)
        full = coala_project(w, x, rank=4)
        chunked = coala_project(w, x, rank=4, chunk_tokens=128)
        np.testing.assert_allclose(np.asarray(full), np.asarray(chunked),
                                   rtol=1e-4, atol=1e-5)


class TestRegularization:
    """Prop. 3 + Eq. (5) + Theorem 1."""

    def test_augmentation_equivalence(self):
        w, x = _rand(16, 10, 8), _rand(10, 6, 9)     # k < n: ill-posed
        mu = 0.3
        via_aug = coala_project(w, x, rank=3, mu=mu)
        x_tilde = jnp.concatenate([x, jnp.sqrt(mu) * jnp.eye(10)], axis=1)
        direct = coala_project(w, x_tilde, rank=3)
        np.testing.assert_allclose(np.asarray(via_aug), np.asarray(direct),
                                   rtol=1e-4, atol=1e-5)

    def test_regularized_objective_optimal(self):
        w, x = _rand(16, 10, 10), _rand(10, 6, 11)
        mu, r = 0.5, 3
        w_mu = coala_project(w, x, rank=r, mu=mu)
        x_tilde = jnp.concatenate([x, jnp.sqrt(mu) * jnp.eye(10)], axis=1)
        err = weighted_error(w, w_mu, x_tilde)
        opt = theory.optimal_weighted_error(w, x_tilde, r)
        np.testing.assert_allclose(err, opt, rtol=1e-4, atol=1e-5)

    def test_thm1_bound_holds_and_linear(self):
        w, x = _rand(16, 12, 12), _rand(12, 8, 13)   # rank-deficient X
        r = 3
        w0 = coala_project(w, x, rank=r, mu=0.0)
        errs = []
        for mu in [1e-3, 1e-4, 1e-5]:
            w_mu = coala_project(w, x, rank=r, mu=mu)
            diff = float(jnp.linalg.norm(w0 - w_mu))
            bound = float(theory.thm1_bound(w, x, r, mu))
            assert diff <= bound * (1 + 1e-3), f"mu={mu}: {diff} > {bound}"
            errs.append(diff)
        # linear convergence: error drops ~10x per decade of mu
        assert errs[1] < errs[0] * 0.5 and errs[2] < errs[1] * 0.5

    def test_eq5_mu_from_lambda(self):
        w, x = _rand(20, 12, 14), _rand(12, 100, 15)
        r_factor = r_from_x(x)
        res = coala_factors(w, x, rank=4, lam=4.0)
        w0 = coala_project(w, x, rank=4)
        expect = 4.0 * float(weighted_error(w, w0, x) ** 2) / \
            float(jnp.sum((w0 - w) ** 2))
        np.testing.assert_allclose(res.mu, expect, rtol=1e-3)
        # and mu_from_lambda agrees when fed R directly
        mu2 = float(mu_from_lambda(w, w0, r_factor, 4.0))
        np.testing.assert_allclose(mu2, expect, rtol=1e-3)


class TestProposition4:
    def test_alpha0_is_pissa(self):
        """α=0: plain EYM subspace of W."""
        w, x = _rand(18, 12, 16), _rand(12, 40, 17)
        a, b = coala_alpha_factors(w, x, rank=4, alpha=0.0)
        np.testing.assert_allclose(np.asarray(a @ b), np.asarray(eym_truncate(w, 4)),
                                   rtol=1e-4, atol=1e-5)

    def test_alpha1_equals_algorithm1(self):
        w, x = _rand(18, 12, 18), _rand(12, 40, 19)
        a, b = coala_alpha_factors(w, x, rank=4, alpha=1.0)
        np.testing.assert_allclose(np.asarray(a @ b),
                                   np.asarray(coala_project(w, x, rank=4)),
                                   rtol=1e-4, atol=1e-5)

    def test_alpha2_matches_corda_objective(self):
        """α=2 solves min ||(W−W')XXᵀ||_F; compare against CorDA on a
        well-conditioned X where the fragile path still works."""
        w = _rand(18, 12, 20)
        x = _rand(12, 200, 21) + 0.1 * jnp.eye(12, 200)
        a, b = coala_alpha_factors(w, x, rank=4, alpha=2.0)
        ac, bc = baselines.corda(w, x, rank=4)
        gram = x @ x.T
        err_ours = jnp.linalg.norm((w - a @ b) @ gram)
        err_corda = jnp.linalg.norm((w - ac @ bc) @ gram)
        np.testing.assert_allclose(float(err_ours), float(err_corda),
                                   rtol=1e-3)

    def test_alpha1_with_mu_equals_algorithm2(self):
        """Regression: the α=1 fast path used to swallow any μ >= 0 — a
        regularized request silently returned the unregularized solution.
        With μ > 0 the α-path must match Algorithm 2 and differ from μ=0."""
        w, x = _rand(16, 10, 40), _rand(10, 6, 41)       # k < n: ill-posed
        mu = 0.5
        a, b = coala_alpha_factors(w, x, rank=3, alpha=1.0, mu=mu)
        res = coala_factors(w, x, rank=3, mu=mu)
        np.testing.assert_allclose(np.asarray(a @ b), np.asarray(res.w_approx),
                                   rtol=1e-4, atol=1e-5)
        w0 = coala_project(w, x, rank=3)                 # μ = 0 solution
        assert float(jnp.linalg.norm(a @ b - w0)) > 1e-3

    def test_alpha2_with_mu_matches_direct_reference(self):
        """μ-regularized α-family against a direct fp64 eigendecomposition
        of W((XXᵀ)^α + μI)Wᵀ."""
        w, x = _rand(18, 12, 42), _rand(12, 40, 43)
        mu, r = 0.7, 4
        a, b = coala_alpha_factors(w, x, rank=r, alpha=2.0, mu=mu)
        w64, x64 = np.asarray(w, np.float64), np.asarray(x, np.float64)
        gram = x64 @ x64.T
        weight = gram @ gram + mu * np.eye(12)           # (XXᵀ)² + μI
        evals, evecs = np.linalg.eigh(w64 @ weight @ w64.T)
        u_r = evecs[:, np.argsort(evals)[::-1][:r]]
        ref = u_r @ u_r.T @ w64
        np.testing.assert_allclose(np.asarray(a @ b), ref, rtol=1e-3,
                                   atol=1e-4)

    def test_negative_mu_raises(self):
        w, x = _rand(18, 12, 44), _rand(12, 40, 45)
        with pytest.raises(ValueError, match="non-negative"):
            coala_alpha_factors(w, x, rank=4, alpha=1.0, mu=-0.5)


class TestBalancedSplit:
    def test_geometric_mean_for_arbitrary_factors(self):
        """Regression: the old scale sqrt(||B row||) assumed orthonormal A
        columns; for arbitrary factors it left ||A col|| and ||B row||
        unequal. The fix must equalize both at the geometric mean while
        preserving the product, for badly scaled A."""
        a = np.asarray(_rand(20, 5, 46)) * \
            np.array([1e-3, 1e-2, 1.0, 1e2, 1e3])[None, :]
        b = np.asarray(_rand(5, 14, 47))
        a2, b2 = balanced_split(jnp.asarray(a), jnp.asarray(b))
        np.testing.assert_allclose(np.asarray(a2 @ b2), a @ b,
                                   rtol=1e-4, atol=1e-5)
        an = np.linalg.norm(np.asarray(a2), axis=0)
        bn = np.linalg.norm(np.asarray(b2), axis=1)
        np.testing.assert_allclose(an, bn, rtol=1e-4)
        geo = np.sqrt(np.linalg.norm(a, axis=0) * np.linalg.norm(b, axis=1))
        np.testing.assert_allclose(an, geo, rtol=1e-4)

    def test_orthonormal_a_keeps_old_behavior(self):
        """With orthonormal A columns (the COALA U_r case) the geometric
        mean reduces to the old sqrt(||B row||) scaling."""
        a = jnp.linalg.qr(_rand(20, 5, 48))[0]
        b = _rand(5, 14, 49)
        a2, b2 = balanced_split(a, b)
        expect = np.sqrt(np.linalg.norm(np.asarray(b), axis=1))
        np.testing.assert_allclose(np.linalg.norm(np.asarray(a2), axis=0),
                                   expect, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(a2 @ b2), np.asarray(a @ b),
                                   rtol=1e-4, atol=1e-5)


class TestRSVD:
    def test_rsvd_subspace_accuracy(self):
        # decaying spectrum (realistic activation statistics -> clear gap)
        u = jnp.linalg.qr(_rand(64, 48, 22))[0]
        v = jnp.linalg.qr(_rand(48, 48, 23))[0]
        s = jnp.logspace(0, -3, 48).astype(jnp.float32)
        m = (u * s[None, :]) @ v.T
        u_exact = jnp.linalg.svd(m, full_matrices=False)[0][:, :8]
        u_rand = rsvd_left_singvecs(m, 8, oversample=8, power_iters=3)
        d = float(theory.projector_distance(u_exact, u_rand))
        assert d < 5e-2, d

    def test_rsvd_coala_error_close_to_exact(self):
        w, x = _rand(64, 48, 24), _rand(48, 256, 25)
        exact = weighted_error(w, coala_project(w, x, rank=8), x)
        rnd = weighted_error(
            w, coala_project(w, x, rank=8, use_rsvd=True, rsvd_power_iters=3), x)
        assert float(rnd) <= float(exact) * 1.05


class TestPrecisionScope:
    """Calibration and compression entry points run their matmuls at
    "highest" precision (a single bf16 pass on TPU otherwise);
    ``__wrapped__`` is the unscoped function."""

    def test_entry_points_run_at_highest(self):
        from repro.core.precision import highest_precision
        seen = []

        @highest_precision
        def probe():
            seen.append(jax.config.jax_default_matmul_precision)

        probe()
        probe.__wrapped__()
        assert seen[0] == "highest"
        assert jax.config.jax_default_matmul_precision == seen[1]
        from repro.core.calibrate import calibrate_model
        from repro.core.compress import compress_model
        from repro.dist.calibrate import calibrate_sharded
        from repro.serve.recalibrate import TrafficCalibrator
        for fn in (coala_factors, coala_alpha_factors, weighted_error,
                   calibrate_model, calibrate_sharded, compress_model,
                   TrafficCalibrator.capture):
            assert hasattr(fn, "__wrapped__"), fn

    def test_unscoped_solve_matches_on_cpu(self):
        w, x = _rand(24, 16, 40), _rand(16, 48, 41)
        a = coala_factors(w, x, rank=4)
        b = coala_factors.__wrapped__(w, x, rank=4)
        np.testing.assert_allclose(np.asarray(a.w_approx),
                                   np.asarray(b.a @ b.b), rtol=1e-4,
                                   atol=1e-5)


class TestStability:
    """The paper's Fig. 1 / Example G.1: Gram-based methods lose √ε accuracy."""

    def _ill_conditioned(self, n=32, k=64, cond=1e7, key=30):
        u = jnp.linalg.qr(_rand(n, n, key))[0]
        v = jnp.linalg.qr(_rand(k, n, key + 1))[0]
        s = jnp.logspace(0, -np.log10(cond), n).astype(jnp.float32)
        return (u * s[None, :]) @ v.T                    # X: (n, k)

    def test_qr_path_beats_gram_paths_when_ill_conditioned(self):
        # cond pinned at 1e9: Gram conditioning is cond^2 = 1e18 >> 1/eps32,
        # so the Gram path degrades on every BLAS (at the seed default of
        # 1e7 some BLAS kept it accurate and the 10x margin never opened);
        # measured margin at this seed is ~29x
        w = _rand(24, 32, 31)
        x = self._ill_conditioned(cond=1e9)
        r = 6
        # fp64 ground truth via numpy
        w64, x64 = np.asarray(w, np.float64), np.asarray(x, np.float64)
        m = w64 @ x64
        u = np.linalg.svd(m)[0][:, :r]
        w_ref = u @ u.T @ w64

        def rel(w_apx):
            return np.linalg.norm(np.asarray(w_apx, np.float64) - w_ref, 2) / \
                np.linalg.norm(w_ref, 2)

        coala_err = rel(coala_project(w, x, rank=r))
        gram = x @ x.T
        a, b = baselines.svd_llm_v2(w, gram, r)
        v2_err = rel(a @ b)
        assert coala_err < 1e-2, coala_err
        # Gram-based path degrades by orders of magnitude (or NaNs)
        assert not np.isfinite(v2_err) or v2_err > 10 * coala_err

    def test_cholesky_fails_on_singular_gram(self):
        """Rank-deficient X: SVD-LLM's Cholesky produces non-finite factors,
        COALA stays finite and optimal."""
        w = _rand(16, 24, 32)
        x_thin = _rand(24, 8, 33)                        # rank 8 < n=24
        gram = x_thin @ x_thin.T
        a, b = baselines.svd_llm(w, gram, 4)
        assert not np.all(np.isfinite(np.asarray(a @ b)))
        w_apx = coala_project(w, x_thin, rank=4)
        assert np.all(np.isfinite(np.asarray(w_apx)))
