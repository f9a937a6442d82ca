"""Distributed behaviours on fake multi-device meshes (subprocess: device
count is locked at jax init, so each scenario runs in its own interpreter)."""
import os
import subprocess
import sys
import textwrap

# These subprocesses exercise `repro.dist` (sharding specs, butterfly TSQR
# under jax.shard_map) and multi-pod training on 8 fake host devices.

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


class TestDistributedTSQR:
    def test_butterfly_equals_serial(self):
        run_with_devices("""
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import PartitionSpec as P
            from repro.core.tsqr import distributed_tsqr_r, qr_r, square_r
            mesh = jax.make_mesh((8,), ("data",),
                                 axis_types=(jax.sharding.AxisType.Auto,))
            xt = jax.random.normal(jax.random.PRNGKey(0), (128, 24))
            f = jax.jit(jax.shard_map(lambda x: distributed_tsqr_r(x, "data"),
                                      mesh=mesh, in_specs=P("data", None),
                                      out_specs=P(), check_vma=False))
            r = f(xt)
            np.testing.assert_allclose(np.asarray(r),
                                       np.asarray(square_r(qr_r(xt))),
                                       rtol=2e-4, atol=2e-4)
            print("OK")
        """)


class TestMoEShardMap:
    def test_sharded_matches_local(self):
        run_with_devices("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs import get_smoke_config
            from repro.models import ffn as ffn_lib
            from repro.models.common import ParallelCtx
            cfg = get_smoke_config("deepseek_moe_16b")
            mesh = jax.make_mesh((2, 4), ("data", "model"),
                                 axis_types=(jax.sharding.AxisType.Auto,)*2)
            params = ffn_lib.moe_init(jax.random.PRNGKey(0), cfg)
            x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
            y_loc, aux_loc = ffn_lib.moe_apply(cfg, params, x, ctx=ParallelCtx())
            ctx = ParallelCtx(mesh=mesh, batch_axes=("data",),
                              shard_map_moe=True)
            y_shd, aux_shd = jax.jit(
                lambda p, x: ffn_lib.moe_apply(cfg, p, x, ctx=ctx))(params, x)
            # same routing math; capacity differs (per-shard), so compare
            # loosely on values and tightly on shapes/finite-ness
            assert y_shd.shape == y_loc.shape
            assert np.all(np.isfinite(np.asarray(y_shd)))
            diff = np.abs(np.asarray(y_shd) - np.asarray(y_loc)).max()
            scale = np.abs(np.asarray(y_loc)).max()
            assert diff < 0.3 * scale, (diff, scale)
            print("OK")
        """)

    def test_sharded_exact_with_full_capacity(self):
        run_with_devices("""
            import jax, jax.numpy as jnp, numpy as np, dataclasses
            from repro.configs import get_smoke_config
            from repro.models import ffn as ffn_lib
            from repro.models.common import ParallelCtx
            cfg = get_smoke_config("jamba_v0_1_52b")
            mesh = jax.make_mesh((2, 4), ("data", "model"),
                                 axis_types=(jax.sharding.AxisType.Auto,)*2)
            params = ffn_lib.moe_init(jax.random.PRNGKey(0), cfg)
            x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
            ctx0 = ParallelCtx(moe_capacity_factor=64.0)
            y_loc, _ = ffn_lib.moe_apply(cfg, params, x, ctx=ctx0)
            ctx = ParallelCtx(mesh=mesh, batch_axes=("data",),
                              shard_map_moe=True, moe_capacity_factor=64.0)
            y_shd, _ = jax.jit(
                lambda p, x: ffn_lib.moe_apply(cfg, p, x, ctx=ctx))(params, x)
            np.testing.assert_allclose(np.asarray(y_shd), np.asarray(y_loc),
                                       rtol=2e-3, atol=2e-3)
            print("OK")
        """)


def _grad_compress_case(n: int) -> None:
    run_with_devices(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.train import grad_compress as gc
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*3)

        def loss_and_grad(params, batch):
            # per-pod quadratic: grads differ across pods via the batch
            def loss(p):
                return jnp.mean((p["w"] * batch["x"] - 1.0) ** 2)
            l, g = jax.value_and_grad(loss)(params)
            return (l, {{"ce": l, "aux": jnp.zeros(())}}), g

        f = gc.make_compressed_grads_fn(
            loss_and_grad, mesh,
            lambda leaf: P("pod", *([None] * (leaf.ndim - 1))))
        params = {{"w": jnp.ones(({n},))}}
        batch = {{"x": jnp.concatenate([jnp.ones((2, {n})),
                                        2 * jnp.ones((2, {n}))])}}
        err = gc.init_error_state(params, 2)
        loss, metrics, grads, new_err = jax.jit(f)(params, batch, err)
        # true mean-of-pod-grads: per pod, loss = mean over (2,n)
        # elements; d/dw_i = (1/(2*n)) * sum_rows 2*(w_i*x-1)*x
        g1 = 2 * 2 * (1.0 - 1.0) * 1.0 / (2 * {n})   # pod 0 (x=1): 0
        g2 = 2 * 2 * (2.0 - 1.0) * 2.0 / (2 * {n})   # pod 1 (x=2)
        want = (g1 + g2) / 2
        np.testing.assert_allclose(np.asarray(grads["w"]),
                                   want * np.ones({n}), rtol=2e-2,
                                   atol=1e-4)
        np.testing.assert_allclose(float(loss), 0.5, rtol=1e-5)
        assert new_err["w"].shape == (2, {n})
        print("OK")
    """)


class TestGradCompression:
    def test_compressed_mean_close_and_error_feedback_accumulates(self):
        _grad_compress_case(256)

    def test_padded_leaf_quantizes_inside_manual_region(self):
        # 250 is not a multiple of the 128-element quantization block, so
        # the pad runs inside the pod-manual shard_map region
        _grad_compress_case(250)


class TestShardedTrainStep:
    def test_small_mesh_train_step_runs(self):
        run_with_devices("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs import get_smoke_config
            from repro.models import build_model
            from repro.models.common import ParallelCtx
            from repro.config import TrainConfig
            from repro.dist.sharding import param_specs, batch_specs, to_named, batch_axes_of
            from repro.train.train_loop import make_train_step, make_train_state
            from jax.sharding import PartitionSpec as P
            mesh = jax.make_mesh((2, 4), ("data", "model"),
                                 axis_types=(jax.sharding.AxisType.Auto,)*2)
            cfg = get_smoke_config("smollm_135m")
            model = build_model(cfg)
            tcfg = TrainConfig(microbatches=2, remat="full")
            ctx = ParallelCtx(mesh=mesh, batch_axes=batch_axes_of(mesh))
            state = make_train_state(model, tcfg, jax.random.PRNGKey(0))
            batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                                  (4, 64), 0, cfg.vocab_size)}
            pspecs = param_specs(cfg, state["params"], mesh, mode="train")
            sspecs = {"params": pspecs, "opt": {"m": pspecs, "v": pspecs,
                                                "step": P()}}
            bspecs = batch_specs(cfg, batch, mesh)
            step = make_train_step(model, tcfg, ctx, mesh=mesh)
            jstep = jax.jit(step, in_shardings=(to_named(sspecs, mesh),
                                                to_named(bspecs, mesh)))
            new_state, metrics = jstep(state, batch)
            assert np.isfinite(float(metrics["loss"]))
            assert int(new_state["opt"]["step"]) == 1
            print("OK")
        """)
