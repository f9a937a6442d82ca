"""Sharded Gram-free calibration (repro.dist.calibrate) on fake meshes.

Contracts under test (subprocess: jax locks device count at init, so each
scenario runs in its own interpreter with 8 fake host devices):

  * shard-count invariance — per-layer R factors from ``calibrate_sharded``
    on 1, 4 and 8 data shards all match, and match the single-device
    ``Calibrator`` output, within fp32 tolerance (R is unique for full-rank
    X under the non-negative-diagonal sign convention);
  * the on-mesh butterfly reduce equals the serial TSQR tree;
  * numerical stability survives the distributed reduction — the sharded
    QR path stays near the fp64 oracle on ill-conditioned calibration data
    while the (equally distributed) Gram accumulation path degrades, the
    mesh-scale mirror of test_coala's
    ``test_qr_path_beats_gram_paths_when_ill_conditioned``.
"""
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_shard_count_invariance_and_single_device_parity():
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.core.calibrate import calibrate_model
        from repro.core.tsqr import qr_r, square_r, tsqr_tree
        from repro.data import DataConfig, TokenPipeline
        from repro.dist.calibrate import calibrate_sharded, combine_r_shards
        cfg = get_smoke_config("smollm_135m")
        from repro.models import build_model
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        pipe = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size, seq_len=64,
                                        global_batch=8, seed=3), cfg)
        batches = [pipe.get_batch(i) for i in range(2)]
        single = calibrate_model(model, params, batches).r_factors()
        assert single, "no layers calibrated"
        meshes = {n: jax.make_mesh((n,), ("data",),
                                   devices=jax.devices()[:n],
                                   axis_types=(jax.sharding.AxisType.Auto,))
                  for n in (1, 4, 8)}
        sharded = {n: calibrate_sharded(model, params, batches, m)
                   for n, m in meshes.items()}
        for n, m in meshes.items():
            # each shard's R factors were built on that shard's own device
            assert sharded[n].shard_devices == tuple(m.devices.reshape(-1)), \
                (n, sharded[n].shard_devices)
        results = {n: s.r_factors() for n, s in sharded.items()}
        for n, rf in results.items():
            assert set(rf) == set(single), (n, sorted(rf), sorted(single))
        # R is unique up to a left-orthogonal factor whose entrywise effect
        # grows with cond(X): compare entrywise where X is well-conditioned,
        # and always as the quadratic form R^T R (the object COALA's
        # weighted projection is invariant under — W R'^T = W R^T Q^T shares
        # singular structure with W R^T for any orthogonal Q)
        worst = None
        for path, ref in single.items():
            ref = np.asarray(ref)
            sv = np.linalg.svd(ref, compute_uv=False)
            cond = sv[0] / max(sv[-1], 1e-30)
            if worst is None or cond > worst[1]:
                worst = (path, cond)
            gram_ref = ref.T @ ref
            for n, rf in results.items():
                got = np.asarray(rf[path])
                grel = np.linalg.norm(got.T @ got - gram_ref) \\
                    / np.linalg.norm(gram_ref)
                assert grel <= 2e-3, (path, n, grel)
                if cond < 1e5:
                    tol = 5e-3 * max(1.0, float(np.abs(ref).max()))
                    err = float(np.abs(got - ref).max())
                    assert err <= tol, (path, n, err, tol)
        # the ill-conditioned layer: downstream COALA projections agree even
        # though R itself is only defined up to the orthogonal factor
        from repro.core.coala import coala_project
        path, _ = worst
        w = jax.random.normal(jax.random.PRNGKey(9),
                              (24, single[path].shape[0]), jnp.float32)
        ref_proj = np.asarray(coala_project(w, r_factor=single[path], rank=6))
        for n, rf in results.items():
            got_proj = np.asarray(coala_project(w, r_factor=rf[path], rank=6))
            rel = np.linalg.norm(got_proj - ref_proj) \\
                / np.linalg.norm(ref_proj)
            assert rel <= 2e-3, (path, n, rel)

        # butterfly reduce == serial TSQR tree on raw random chunks
        chunks = [jax.random.normal(jax.random.PRNGKey(10 + i), (40, 16))
                  for i in range(8)]
        r_serial = square_r(tsqr_tree(chunks))
        r_stack = jnp.stack([square_r(qr_r(c)) for c in chunks])
        r_bfly = combine_r_shards(r_stack, meshes[8], axis="data")
        np.testing.assert_allclose(np.asarray(r_bfly), np.asarray(r_serial),
                                   rtol=2e-4, atol=2e-4)
        print("OK")
    """)


def test_sharded_qr_beats_gram_when_ill_conditioned():
    # cond pinned at 1e9 (as in test_coala): Gram conditioning is 1e18 >>
    # 1/eps32, so the distributed Gram sum degrades on every BLAS while the
    # per-shard QR + butterfly reduce stays near the fp64 oracle
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import baselines
        from repro.core.coala import coala_project
        from repro.core.tsqr import qr_r, square_r
        from repro.dist.calibrate import combine_r_shards
        n, k, rank, cond, shards = 32, 512, 6, 1e9, 8
        def rand(a, b, key):
            return jax.random.normal(jax.random.PRNGKey(key), (a, b),
                                     jnp.float32)
        u = jnp.linalg.qr(rand(n, n, 30))[0]
        v = jnp.linalg.qr(rand(k, n, 31))[0]
        s = jnp.logspace(0, -np.log10(cond), n).astype(jnp.float32)
        x = (u * s[None, :]) @ v.T                       # X: (n, k)
        w = rand(24, n, 32)

        # fp64 ground truth
        w64, x64 = np.asarray(w, np.float64), np.asarray(x, np.float64)
        uu = np.linalg.svd(w64 @ x64)[0][:, :rank]
        w_ref = uu @ uu.T @ w64
        def rel(w_apx):
            return np.linalg.norm(np.asarray(w_apx, np.float64) - w_ref, 2) \\
                / np.linalg.norm(w_ref, 2)

        # shard the token rows of X^T; per-shard local R, butterfly reduce
        xt_shards = [x.T[i * (k // shards):(i + 1) * (k // shards)]
                     for i in range(shards)]
        mesh = jax.make_mesh((shards,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        r_stack = jnp.stack([square_r(qr_r(xs)) for xs in xt_shards])
        r_dist = combine_r_shards(r_stack, mesh, axis="data")
        coala_err = rel(coala_project(w, r_factor=r_dist, rank=rank))

        # the distributed Gram path: per-shard Gram partials, summed
        gram = sum(xs.T @ xs for xs in xt_shards)
        a, b = baselines.svd_llm_v2(w, gram, rank)
        v2_err = rel(a @ b)

        assert coala_err < 1e-2, coala_err
        assert not np.isfinite(v2_err) or v2_err > 10 * coala_err, \\
            (coala_err, v2_err)
        print("OK")
    """)


def test_moe_expert_paths_match_single_device():
    # a MoE expert's routed rows are not split like the batch: the capture
    # routes the whole batch at once (as one device does) and zero-pads
    # each record's rows to the shard count, so every expert path sees the
    # single-device rows and token counts
    run_with_devices("""
        import jax, numpy as np
        from repro.configs import get_smoke_config
        from repro.core.calibrate import calibrate_model
        from repro.data import DataConfig, TokenPipeline
        from repro.dist.calibrate import calibrate_sharded
        from repro.models import build_model
        cfg = get_smoke_config("deepseek_moe_16b")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        pipe = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                        global_batch=4, seed=3), cfg)
        batches = [pipe.get_batch(i) for i in range(2)]
        single = calibrate_model(model, params, batches)
        mesh = jax.make_mesh((4,), ("data",), devices=jax.devices()[:4])
        sharded = calibrate_sharded(model, params, batches, mesh)
        rs, rd = single.r_factors(), sharded.r_factors()
        assert any("/expert" in p for p in rs), sorted(rs)
        assert set(rd) == set(rs), sorted(set(rd) ^ set(rs))
        assert sharded.tokens_seen() == single.tokens_seen()
        for p, r in rs.items():
            g = np.asarray(r, np.float64).T @ np.asarray(r, np.float64)
            d = np.asarray(rd[p], np.float64)
            rel = np.linalg.norm(d.T @ d - g) / np.linalg.norm(g)
            assert rel < 1e-3, (p, rel)
        print("OK")
    """, n=4)
