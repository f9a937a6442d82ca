"""Paged-attention decode Pallas kernel: block-table indirection, no gather.

One decode step attends a single query token per request against that
request's KV history, which lives scattered across fixed-size *pages* of a
shared block pool (``serve/paged_cache.py``) and is addressed through a
per-request block table. The previous read path gathered every request's
pages into a contiguous ``(B, T, Hkv, hd)`` view before calling attention —
a full-cache copy per decode step. This kernel reads the indirection
directly:

  * ``block_tables (B, nb)`` and ``lengths (B,)`` ride in SMEM as
    scalar-prefetch arguments (``pltpu.PrefetchScalarGridSpec``), available
    before the kernel body runs so they can steer the DMA;
  * the K/V BlockSpec index maps resolve ``tables[b, i]`` per grid step, so
    each KV page is fetched from HBM exactly once, block-by-block — HBM
    traffic is O(tokens attended), never O(pool);
  * grid ``(B, Hkv, nb)`` with the page axis innermost ("arbitrary"):
    online-softmax state (m, l, acc) for the G = Hq/Hkv query heads sharing
    a KV head lives in VMEM scratch and is carried across pages — GQA means
    K/V traffic scales with Hkv, not Hq;
  * pages past ``ceil(len/bs)`` and (with a sliding window) pages wholly
    below the window are skipped via ``pl.when`` — padding rows in a
    bucketed batch (length ≤ 1, table full of the trash block) cost one
    masked page at most.

``interpret=True`` runs the same program as traced JAX ops, so CPU CI
executes the kernel body bit-for-bit; ``paged_attention_ref`` is the plain
``jax.nn`` fallback for backends without Pallas support (and the parity
oracle in tests). ``kernels/chunked_prefill.py`` is this kernel's
prefill-shaped sibling (batched suffix prefill over the same pages);
docs/kernels.md documents both grids and the SMEM prefetch layout, and
docs/serving.md the page/block/bucket vocabulary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *,
            scale: float, cap: float, window: int, bs: int, nb: int):
    b = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    live = i * bs < length                     # page holds valid positions
    if window > 0:                             # page not wholly below window
        live &= (i + 1) * bs > length - window

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0]                        # (G, hd)
        k = k_ref[0, 0]                        # (bs, hd)
        v = v_ref[0, 0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if cap > 0:
            s = cap * jnp.tanh(s / cap)
        ik = i * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = ik < length                       # causal: q sits at length-1
        if window > 0:
            ok &= (length - 1 - ik) < window
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]                    # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(i == nb - 1)
    def _finalize():
        # zero-length rows (bucket padding) finalize with l == 0 -> output 0
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "cap", "window",
                                             "interpret"))
def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    scale=None, cap: float = 0.0, window: int = 0,
                    interpret: bool = False):
    """Decode-step attention over a paged KV cache.

    q: (B, Hq, hd) — one query token per request, already rotary-embedded.
    k_pages/v_pages: (num_blocks, Hkv, bs, hd) — the shared block pool,
      head-major so each (page, head) block is a full ``(bs, hd)`` tile.
    block_tables: (B, nb) int32 — physical page ids per request, ragged rows
      padded with the trash block (0).
    lengths: (B,) int32 — valid positions per request (query at length-1);
      0 marks a bucket-padding row and yields a zero output row.

    Returns (B, Hq, hd) in q.dtype.
    """
    b, hq, hd = q.shape
    nb_total, hkv, bs, _ = k_pages.shape
    g = hq // hkv
    nb = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    qg = q.reshape(b, hkv, g, hd)
    tables = block_tables.astype(jnp.int32)
    lens = lengths.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # tables, lengths -> SMEM
        grid=(b, hkv, nb),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda bi, h, i, tbl, ln: (bi, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd),
                         lambda bi, h, i, tbl, ln: (tbl[bi, i], h, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd),
                         lambda bi, h, i, tbl, ln: (tbl[bi, i], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda bi, h, i, tbl, ln: (bi, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),   # running max m
            pltpu.VMEM((g, 1), jnp.float32),   # running denom l
            pltpu.VMEM((g, hd), jnp.float32),  # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, cap=cap, window=window,
                          bs=bs, nb=nb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, hd), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(tables, lens, qg, k_pages, v_pages)
    return out.reshape(b, hq, hd)


def gather_pages(pages, block_tables):
    """(num_blocks, Hkv, bs, hd) pages named by (B, nb) tables ->
    contiguous (B, nb * bs, Hkv, hd) token-major K/V."""
    b, nb = block_tables.shape
    _, hkv, bs, hd = pages.shape
    g = pages[block_tables]                          # (B, nb, Hkv, bs, hd)
    return g.transpose(0, 1, 3, 2, 4).reshape(b, nb * bs, hkv, hd)


def paged_attention_ref(q, k_pages, v_pages, block_tables, lengths, *,
                        scale=None, cap: float = 0.0, window: int = 0):
    """``jax.nn`` fallback for backends without Pallas, and the test oracle.

    Gathers only the pages named by the block tables (O(tokens attended),
    inside the surrounding jit) and runs a masked softmax in fp32.
    """
    b, hq, hd = q.shape
    hkv, bs = k_pages.shape[1], k_pages.shape[2]
    g = hq // hkv
    nb = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    k = gather_pages(k_pages, block_tables)
    v = gather_pages(v_pages, block_tables)
    qg = q.reshape(b, hkv, g, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if cap > 0:
        s = cap * jnp.tanh(s / cap)
    ik = jnp.arange(nb * bs)
    ok = ik[None] < lengths[:, None]
    if window > 0:
        ok &= (lengths[:, None] - 1 - ik[None]) < window
    s = jnp.where(ok[:, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jnp.maximum(m, NEG_INF / 2))   # all-masked rows -> p ~ 0
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bkgs,bskd->bkgd", p / jnp.maximum(l, 1e-30),
                   v.astype(jnp.float32))
    return o.reshape(b, hq, hd).astype(q.dtype)
