"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rand(shape, key, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32).astype(dtype)


class TestLowrankLinear:
    @pytest.mark.slow
    @pytest.mark.parametrize("m,d_in,r,d_out", [
        (256, 512, 128, 512), (512, 256, 128, 1024),
        (256, 128, 128, 128), (300, 200, 64, 150),   # fallback path (non-divisible)
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, m, d_in, r, d_out, dtype):
        x = _rand((m, d_in), 0, dtype)
        b_t = _rand((d_in, r), 1, dtype)
        a_t = _rand((r, d_out), 2, dtype)
        got = ops.lowrank_linear(x, b_t, a_t, block_m=128, block_n=128)
        want = ref.lowrank_linear_ref(x, b_t, a_t)
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol * 10)

    def test_batched_input(self):
        x = _rand((2, 32, 256), 3)
        b_t, a_t = _rand((256, 128), 4), _rand((128, 256), 5)
        got = ops.lowrank_linear(x, b_t, a_t, block_m=64, block_n=128)
        want = ref.lowrank_linear_ref(x, b_t, a_t)
        assert got.shape == (2, 32, 256)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


class TestGramAccum:
    @pytest.mark.slow
    @pytest.mark.parametrize("k,n", [(1024, 256), (512, 512), (100, 96)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, k, n, dtype):
        a = _rand((k, n), 6, dtype)
        got = ops.gram_accum(a, block_i=128, block_j=128, block_k=256)
        want = ref.gram_accum_ref([a])
        tol = 5e-2 if dtype == jnp.bfloat16 else 1e-3
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=tol, atol=tol)

    def test_chunked_sum_equals_full(self):
        a = _rand((2048, 128), 7)
        g = sum(ops.gram_accum(a[i:i + 512], block_k=256)
                for i in range(0, 2048, 512))
        np.testing.assert_allclose(np.asarray(g), np.asarray(ref.gram_accum_ref([a])),
                                   rtol=1e-3, atol=1e-3)


class TestFlashAttention:
    @pytest.mark.slow
    @pytest.mark.parametrize("b,t,hq,hkv,hd", [
        (1, 256, 4, 4, 64),            # MHA
        (2, 256, 8, 2, 64),            # GQA 4:1
        (1, 512, 4, 1, 128),           # MQA
        (1, 192, 3, 1, 64),            # fallback path (non-divisible)
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, b, t, hq, hkv, hd, dtype):
        q = _rand((b, t, hq, hd), 8, dtype)
        k = _rand((b, t, hkv, hd), 9, dtype)
        v = _rand((b, t, hkv, hd), 10, dtype)
        got = ops.flash_attention(q, k, v, block_q=128, block_k=128)
        want = ref.flash_attention_ref(q, k, v)
        tol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)

    def test_softcap(self):
        q = _rand((1, 256, 4, 64), 11)
        k = _rand((1, 256, 4, 64), 12)
        v = _rand((1, 256, 4, 64), 13)
        got = ops.flash_attention(q, k, v, cap=20.0)
        want = ref.flash_attention_ref(q, k, v, cap=20.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_matches_model_chunked_path(self):
        """The model's chunked jnp attention and the kernel agree."""
        from repro.models.attention import _chunked_sdpa
        q = _rand((1, 512, 4, 64), 14)
        k = _rand((1, 512, 2, 64), 15)
        v = _rand((1, 512, 2, 64), 16)
        got = ops.flash_attention(q, k, v)
        want = _chunked_sdpa(q, k, v, q_offset=0, causal=True, window=0,
                             cap=0.0, scale=64 ** -0.5, chunk_q=128, chunk_kv=128)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("case", ["causal", "window", "softcap",
                                      "row_offsets"])
    def test_dense_path_jit_matches_eager_body(self, case):
        """The jitted dense attention, called eagerly as the capture forward
        calls it, computes what its op-by-op body computes."""
        from repro.models.attention import _dense_sdpa
        tq, tk = (4, 96) if case == "row_offsets" else (96, 96)
        q = _rand((2, tq, 6, 32), 17)
        k = _rand((2, tk, 2, 32), 18)
        v = _rand((2, tk, 2, 32), 19)
        kw = dict(q_offset=0, causal=True, window=0, cap=0.0,
                  scale=32 ** -0.5)
        if case == "window":
            kw["window"] = 24
        elif case == "softcap":
            kw["cap"] = 2.0
        elif case == "row_offsets":
            kw["q_offset"] = jnp.asarray([17, 92], jnp.int32)
        with jax.default_matmul_precision("highest"):
            got = np.asarray(_dense_sdpa(q, k, v, **kw))
            want = np.asarray(_dense_sdpa.__wrapped__(q, k, v, **kw))
        assert np.isfinite(got).all()
        rel = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert rel <= 1e-6, rel


class TestModelPallasPath:
    @pytest.mark.slow
    def test_model_forward_with_pallas_attention(self):
        """A whole-model forward through the Pallas flash kernel (interpret
        mode) matches the portable attention path."""
        import dataclasses
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.models.common import ParallelCtx
        cfg = get_smoke_config("olmo_1b")
        cfg = dataclasses.replace(cfg, head_dim=16)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 128),
                                              0, cfg.vocab_size)}
        l_ref, _ = model.loss(params, batch, ctx=ParallelCtx(),
                              compute_dtype=jnp.float32)
        l_pal, _ = model.loss(params, batch, ctx=ParallelCtx(use_pallas=True),
                              compute_dtype=jnp.float32)
        np.testing.assert_allclose(float(l_pal), float(l_ref),
                                   rtol=1e-4, atol=1e-4)
