"""Paged-attention decode kernel vs oracles (interpret=True on CPU).

Three-way parity: the Pallas kernel (interpret mode — the exact program
Mosaic would lower on TPU), the ``jax.nn`` reference fallback, and a dense
numpy oracle that materializes each request's contiguous KV prefix.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.paged_attention import paged_attention, paged_attention_ref


def _case(key, *, b, hq, hkv, hd, bs, num_blocks, lengths, dtype=jnp.float32):
    """Build a random pool + block tables covering ``lengths`` per request."""
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    q = jax.random.normal(ks[0], (b, hq, hd), jnp.float32).astype(dtype)
    k_pages = jax.random.normal(ks[1], (num_blocks, hkv, bs, hd),
                                jnp.float32).astype(dtype)
    v_pages = jax.random.normal(ks[2], (num_blocks, hkv, bs, hd),
                                jnp.float32).astype(dtype)
    # hand out distinct non-trash blocks round-robin; pad rows with block 0
    nb = max(-(-max(lengths, default=1) // bs), 1)
    tables = np.zeros((b, nb), np.int32)
    nxt = 1
    for i, ln in enumerate(lengths):
        for j in range(-(-ln // bs)):
            tables[i, j] = nxt
            nxt += 1
    assert nxt <= num_blocks, "test pool too small"
    return q, k_pages, v_pages, jnp.asarray(tables), \
        jnp.asarray(lengths, jnp.int32)


def _dense_oracle(q, k_pages, v_pages, tables, lengths, *, scale=None,
                  cap=0.0, window=0):
    """Per-request contiguous softmax attention in fp64."""
    q = np.asarray(q, np.float64)
    # head-major pages -> token-major (num_blocks, bs, hkv, hd)
    kp = np.asarray(k_pages, np.float64).transpose(0, 2, 1, 3)
    vp = np.asarray(v_pages, np.float64).transpose(0, 2, 1, 3)
    tables = np.asarray(tables)
    lengths = np.asarray(lengths)
    b, hq, hd = q.shape
    bs, hkv = kp.shape[1], kp.shape[2]
    g = hq // hkv
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    out = np.zeros_like(q)
    for i in range(b):
        ln = int(lengths[i])
        if ln == 0:
            continue
        k = kp[tables[i]].reshape(-1, hkv, hd)[:ln]      # (ln, hkv, hd)
        v = vp[tables[i]].reshape(-1, hkv, hd)[:ln]
        lo = max(0, ln - window) if window > 0 else 0
        for h in range(hq):
            s = (k[lo:, h // g] @ q[i, h]) * scale
            if cap > 0:
                s = cap * np.tanh(s / cap)
            p = np.exp(s - s.max())
            p /= p.sum()
            out[i, h] = p @ v[lo:, h // g]
    return out


CASES = [
    # (hq, hkv, lengths, bs, cap, window)
    (4, 2, [5, 12, 1], 4, 0.0, 0),        # GQA, ragged, partial blocks
    (3, 1, [8, 3], 4, 0.0, 0),            # MQA-style sharing (g=3)
    (2, 2, [7, 16, 9, 2], 8, 0.0, 0),     # MHA, bs=8
    (4, 2, [20, 11], 4, 50.0, 0),         # logit softcap (gemma2)
    (4, 2, [20, 6, 13], 4, 0.0, 8),       # sliding window
    (4, 2, [19, 5], 4, 30.0, 6),          # window + cap together
]


@pytest.mark.parametrize("hq,hkv,lengths,bs,cap,window", CASES)
def test_kernel_matches_dense_oracle(hq, hkv, lengths, bs, cap, window):
    q, kp, vp, tables, lens = _case(0, b=len(lengths), hq=hq, hkv=hkv,
                                    hd=16, bs=bs, num_blocks=16,
                                    lengths=lengths)
    want = _dense_oracle(q, kp, vp, tables, lens, cap=cap, window=window)
    got = paged_attention(q, kp, vp, tables, lens, cap=cap, window=window,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    got_ref = paged_attention_ref(q, kp, vp, tables, lens, cap=cap,
                                  window=window)
    np.testing.assert_allclose(np.asarray(got_ref), want, rtol=2e-5,
                               atol=2e-5)


def test_zero_length_rows_are_zero_and_finite():
    """Bucket-padding rows (length 0, all-trash table) must not NaN."""
    q, kp, vp, tables, lens = _case(1, b=3, hq=4, hkv=2, hd=8, bs=4,
                                    num_blocks=8, lengths=[6, 0, 0])
    for fn in (lambda: paged_attention(q, kp, vp, tables, lens,
                                       interpret=True),
               lambda: paged_attention_ref(q, kp, vp, tables, lens)):
        out = np.asarray(fn())
        assert np.all(np.isfinite(out))
        np.testing.assert_array_equal(out[1:], 0.0)


def test_trash_block_padding_is_ignored():
    """Rows whose tables are padded with block 0 must not read it."""
    q, kp, vp, tables, lens = _case(2, b=2, hq=2, hkv=1, hd=8, bs=4,
                                    num_blocks=8, lengths=[3, 11])
    # poison the trash block: if any masked position leaks, outputs change
    kp2 = kp.at[0].set(1e4)
    vp2 = vp.at[0].set(1e4)
    a = paged_attention(q, kp, vp, tables, lens, interpret=True)
    bb = paged_attention(q, kp2, vp2, tables, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(bb), rtol=1e-6)


def test_ops_dispatch_ref_on_cpu():
    """ops.paged_attention auto-routes to the jax.nn fallback off-TPU."""
    q, kp, vp, tables, lens = _case(3, b=2, hq=4, hkv=2, hd=8, bs=4,
                                    num_blocks=8, lengths=[5, 9])
    auto = ops.paged_attention(q, kp, vp, tables, lens)
    ref = paged_attention_ref(q, kp, vp, tables, lens)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(ref))


@pytest.mark.slow
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("hd", [16, 64])
def test_kernel_dtype_sweep(dtype, hd):
    q, kp, vp, tables, lens = _case(4, b=4, hq=4, hkv=2, hd=hd, bs=8,
                                    num_blocks=16, lengths=[25, 7, 16, 1],
                                    dtype=dtype)
    want = _dense_oracle(q, kp, vp, tables, lens)
    got = paged_attention(q, kp, vp, tables, lens, interpret=True)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=tol, atol=tol)
