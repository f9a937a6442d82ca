"""Chunked-prefill kernel vs oracles (interpret=True on CPU).

Kernel level: three-way parity between the Pallas kernel (interpret mode —
the exact program Mosaic would lower on TPU), the ``jax.nn`` reference
fallback, and a dense fp64 oracle that materializes each row's contiguous
prefix+suffix KV — across GQA/window/softcap, ragged suffix lengths,
prefix-offset causal masks, zero-length rows, and trash-page padding.

Engine level: three-way greedy token parity (chunked-prefill kernel vs the
gather oracle vs the legacy fixed-batch ``ServeEngine``) under a staggered
shared-prefix trace, including with the interpret-mode kernels forced into
the engine.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.chunked_prefill import chunked_prefill, chunked_prefill_ref


def _case(key, *, b, hq, hkv, hd, bs, num_blocks, starts, lens, lq=None):
    """Random pages + tables covering each row's prefix+suffix tokens.

    Pages already hold both the cached-prefix KV and the new suffix KV
    (in the serving path ``models/attention.py`` scatters the suffix in
    before the kernel runs — the kernel itself only reads pages)."""
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    lq = lq or max(max(lens), 1)
    q = jax.random.normal(ks[0], (b, lq, hq, hd), jnp.float32)
    k_pages = jax.random.normal(ks[1], (num_blocks, hkv, bs, hd), jnp.float32)
    v_pages = jax.random.normal(ks[2], (num_blocks, hkv, bs, hd), jnp.float32)
    totals = [s + l for s, l in zip(starts, lens)]
    nb = max(max(-(-t // bs) for t in totals), 1)
    tables = np.zeros((b, nb), np.int32)
    nxt = 1
    for i, t in enumerate(totals):
        for j in range(-(-t // bs)):
            tables[i, j] = nxt
            nxt += 1
    assert nxt <= num_blocks, "test pool too small"
    return (q, k_pages, v_pages, jnp.asarray(tables),
            jnp.asarray(starts, jnp.int32), jnp.asarray(lens, jnp.int32))


def _dense_oracle(q, k_pages, v_pages, tables, starts, lens, *, scale=None,
                  cap=0.0, window=0):
    """Per-row, per-query contiguous softmax attention in fp64; query j of
    row i sits at global position starts[i] + j and attends [0, that]."""
    q = np.asarray(q, np.float64)
    # head-major pages -> token-major (num_blocks, bs, hkv, hd)
    kp = np.asarray(k_pages, np.float64).transpose(0, 2, 1, 3)
    vp = np.asarray(v_pages, np.float64).transpose(0, 2, 1, 3)
    tables, starts, lens = map(np.asarray, (tables, starts, lens))
    b, lq, hq, hd = q.shape
    bs, hkv = kp.shape[1], kp.shape[2]
    g = hq // hkv
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    out = np.zeros_like(q)
    for i in range(b):
        total = int(starts[i] + lens[i])
        k = kp[tables[i]].reshape(-1, hkv, hd)[:total]
        v = vp[tables[i]].reshape(-1, hkv, hd)[:total]
        for j in range(int(lens[i])):
            iq = int(starts[i]) + j
            lo = max(0, iq + 1 - window) if window > 0 else 0
            for h in range(hq):
                s = (k[lo:iq + 1, h // g] @ q[i, j, h]) * scale
                if cap > 0:
                    s = cap * np.tanh(s / cap)
                p = np.exp(s - s.max())
                p /= p.sum()
                out[i, j, h] = p @ v[lo:iq + 1, h // g]
    return out


CASES = [
    # (hq, hkv, starts, lens, bs, cap, window)
    (4, 2, [0, 8, 4], [5, 7, 1], 4, 0.0, 0),     # GQA, ragged, prefix offsets
    (3, 1, [12, 0], [3, 9], 4, 0.0, 0),          # MQA-style sharing (g=3)
    (2, 2, [8, 0, 16], [8, 2, 5], 8, 0.0, 0),    # MHA, bs=8, block-aligned
    (4, 2, [8, 4], [6, 9], 4, 50.0, 0),          # logit softcap (gemma2)
    (4, 2, [16, 0, 8], [5, 11, 3], 4, 0.0, 6),   # sliding window over prefix
    (4, 2, [12, 4], [7, 2], 4, 30.0, 5),         # window + cap together
]


@pytest.mark.parametrize("hq,hkv,starts,lens,bs,cap,window", CASES)
def test_kernel_matches_dense_oracle(hq, hkv, starts, lens, bs, cap, window):
    q, kp, vp, tables, st, ln = _case(0, b=len(starts), hq=hq, hkv=hkv,
                                      hd=16, bs=bs, num_blocks=24,
                                      starts=starts, lens=lens)
    want = _dense_oracle(q, kp, vp, tables, st, ln, cap=cap, window=window)
    got = chunked_prefill(q, kp, vp, tables, st, ln, cap=cap, window=window,
                          block_q=4, interpret=True)
    got_ref = chunked_prefill_ref(q, kp, vp, tables, st, ln, cap=cap,
                                  window=window)
    for i, l in enumerate(lens):
        np.testing.assert_allclose(np.asarray(got)[i, :l], want[i, :l],
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(got_ref)[i, :l], want[i, :l],
                                   rtol=2e-5, atol=2e-5)
        # padded query rows (bucket padding past lens) are exactly zero
        np.testing.assert_array_equal(np.asarray(got)[i, l:], 0.0)
        np.testing.assert_array_equal(np.asarray(got_ref)[i, l:], 0.0)


def test_zero_length_rows_are_zero_and_finite():
    """Batch-padding rows (lens 0, all-trash table) must not NaN — even
    with a nonzero start pointing at a cached prefix."""
    q, kp, vp, tables, st, ln = _case(1, b=3, hq=4, hkv=2, hd=8, bs=4,
                                      num_blocks=12,
                                      starts=[4, 0, 8], lens=[6, 0, 0])
    for fn in (lambda: chunked_prefill(q, kp, vp, tables, st, ln,
                                       block_q=4, interpret=True),
               lambda: chunked_prefill_ref(q, kp, vp, tables, st, ln)):
        out = np.asarray(fn())
        assert np.all(np.isfinite(out))
        np.testing.assert_array_equal(out[1:], 0.0)


def test_trash_page_padding_is_ignored():
    """Ragged table padding points at page 0; poisoning it must not change
    any valid output."""
    q, kp, vp, tables, st, ln = _case(2, b=2, hq=2, hkv=1, hd=8, bs=4,
                                      num_blocks=12,
                                      starts=[0, 8], lens=[3, 6])
    kp2 = kp.at[0].set(1e4)
    vp2 = vp.at[0].set(1e4)
    a = chunked_prefill(q, kp, vp, tables, st, ln, block_q=4, interpret=True)
    bb = chunked_prefill(q, kp2, vp2, tables, st, ln, block_q=4,
                         interpret=True)
    for i, l in enumerate(np.asarray(ln)):
        np.testing.assert_allclose(np.asarray(a)[i, :l],
                                   np.asarray(bb)[i, :l], rtol=1e-6)


def test_query_chunking_invariant():
    """block_q only tiles the grid; outputs must not depend on it."""
    q, kp, vp, tables, st, ln = _case(3, b=2, hq=4, hkv=2, hd=8, bs=4,
                                      num_blocks=16,
                                      starts=[4, 0], lens=[9, 13])
    outs = [np.asarray(chunked_prefill(q, kp, vp, tables, st, ln,
                                       block_q=bq, interpret=True))
            for bq in (2, 4, 16)]
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(outs[0], outs[2], rtol=2e-5, atol=2e-5)


def test_ops_dispatch_ref_on_cpu():
    """ops.chunked_prefill auto-routes to the jax.nn fallback off-TPU."""
    q, kp, vp, tables, st, ln = _case(4, b=2, hq=4, hkv=2, hd=8, bs=4,
                                      num_blocks=12,
                                      starts=[4, 0], lens=[5, 9])
    auto = ops.chunked_prefill(q, kp, vp, tables, st, ln)
    ref = chunked_prefill_ref(q, kp, vp, tables, st, ln)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(ref))


@pytest.mark.slow
@pytest.mark.parametrize("hd", [16, 64])
def test_kernel_large_sweep(hd):
    q, kp, vp, tables, st, ln = _case(5, b=4, hq=4, hkv=2, hd=hd, bs=8,
                                      num_blocks=32,
                                      starts=[24, 0, 8, 16],
                                      lens=[17, 31, 1, 9])
    want = _dense_oracle(q, kp, vp, tables, st, ln)
    got = chunked_prefill(q, kp, vp, tables, st, ln, block_q=8,
                          interpret=True)
    for i, l in enumerate([17, 31, 1, 9]):
        np.testing.assert_allclose(np.asarray(got)[i, :l], want[i, :l],
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Engine level: kernel prefill vs gather oracle vs fixed-batch oracle
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def smollm():
    from repro.configs import get_smoke_config
    from repro.models import build_model
    cfg = get_smoke_config("smollm_135m")
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _cont(model, params, **kw):
    from repro.serve import ContinuousEngine
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("max_running", 4)
    return ContinuousEngine(model, params, compute_dtype=jnp.float32,
                            cache_dtype=jnp.float32, **kw)


def _oracle_tokens(model, params, prompt, n):
    from repro.serve import ServeEngine
    leg = ServeEngine(model, params, compute_dtype=jnp.float32,
                      cache_dtype=jnp.float32)
    return np.asarray(leg.generate(jnp.asarray(prompt)[None],
                                   max_new_tokens=n))[0, len(prompt):]


def _staggered(eng, prompts, news):
    ids = []
    for p, n in zip(prompts, news):
        ids.append(eng.submit(p, n))
        eng.step()                          # join mid-decode
    eng.run()
    fin = {r.req_id: r for r in eng.finished}
    return [np.asarray(fin[i].out_tokens) for i in ids]


def _shared_prefix_prompts(cfg, rng, *, prefix_len, tails):
    shared = rng.randint(0, cfg.vocab_size, (prefix_len,)).astype(np.int32)
    return [np.concatenate(
        [shared, rng.randint(0, cfg.vocab_size, (t,)).astype(np.int32)])
        for t in tails]


def test_engine_prefill_kernel_on_by_default(smollm):
    _, model, params = smollm
    eng = _cont(model, params)
    assert eng.prefill_kernel            # auto-on for pure-attention GQA LMs


def test_engine_parity_shared_prefix_staggered(smollm):
    """Three-way greedy token parity under the staggered shared-prefix
    trace: chunked-prefill kernel path vs the gather oracle vs the
    fixed-batch ServeEngine — with prefix hits, so suffix prefills run at
    nonzero cache offsets."""
    cfg, model, params = smollm
    rng = np.random.RandomState(0)
    prompts = _shared_prefix_prompts(cfg, rng, prefix_len=12, tails=(3, 5, 7))
    prompts.append(rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32))
    news = [5, 5, 4, 5]
    ek = _cont(model, params, prefill_kernel=True)
    out_k = _staggered(ek, prompts, news)
    eg = _cont(model, params, prefill_kernel=False)
    out_g = _staggered(eg, prompts, news)
    assert ek.metrics()["prefix_hit_tokens"] >= 2 * 12
    assert ek.metrics()["prefill_kernel"] == 1.0
    assert eg.metrics()["prefill_kernel"] == 0.0
    for p, n, gk, gg in zip(prompts, news, out_k, out_g):
        ref = _oracle_tokens(model, params, p, n)
        np.testing.assert_array_equal(ref, gk,
                                      err_msg="kernel prefill diverged")
        np.testing.assert_array_equal(ref, gg,
                                      err_msg="gather prefill diverged")


def test_engine_parity_interpret_kernel(smollm):
    """The interpret-mode Pallas kernels (decode + chunked prefill) forced
    into the engine stay on the oracle trajectory — short trace, the CI
    stand-in for native-TPU execution."""
    cfg, model, params = smollm
    rng = np.random.RandomState(1)
    prompts = _shared_prefix_prompts(cfg, rng, prefix_len=8, tails=(2, 5))
    eng = _cont(model, params, prefill_kernel=True, paged_kernel=True,
                paged_attn_impl="pallas")
    out = _staggered(eng, prompts, [4, 4])
    for p, got in zip(prompts, out):
        np.testing.assert_array_equal(_oracle_tokens(model, params, p, 4),
                                      got)


def test_prefill_kernel_rejected_for_unsupported_model():
    """Recurrent/hybrid archs cannot ride the chunked paged path."""
    from repro.configs import get_smoke_config
    from repro.models import build_model
    cfg = get_smoke_config("xlstm_1_3b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = _cont(model, params, prefix_cache=False)
    assert not eng.prefill_kernel
    with pytest.raises(ValueError):
        _cont(model, params, prefix_cache=False, prefill_kernel=True)


@pytest.mark.slow
def test_engine_parity_gemma2_window_softcap():
    """gemma2 local/global windows + logit softcaps through the kernel
    prefill path on a staggered shared-prefix trace."""
    from repro.configs import get_smoke_config
    from repro.models import build_model
    cfg = get_smoke_config("gemma2_27b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(2)
    prompts = _shared_prefix_prompts(cfg, rng, prefix_len=10, tails=(3, 6, 2))
    eng = _cont(model, params, prefill_kernel=True,
                paged_attn_impl="pallas")
    out = _staggered(eng, prompts, [5, 5, 5])
    for p, got in zip(prompts, out):
        np.testing.assert_array_equal(_oracle_tokens(model, params, p, 5),
                                      got)
