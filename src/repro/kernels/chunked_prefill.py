"""Chunked-prefill Pallas kernel: batched suffix prefill over the paged pool.

Batched prefill (``serve/engine.py``) admits a group of requests whose
prompt *suffixes* (the part their cached prefix does not cover) land in the
same length bucket and computes them in one call. Before this kernel the
read path gathered every row's pages into a contiguous ``(B, T, Hkv, hd)``
view and ran dense attention against it — a full per-row cache copy per
prefill, the same tax the decode path shed in ``paged_attention.py``. This
kernel is that kernel's prefill-shaped sibling and reads the block-table
indirection directly:

  * ``block_tables (B, nb)``, ``starts (B,)`` (each row's cached-prefix
    length = its first query's global position) and ``lens (B,)`` (valid
    suffix tokens per row) ride in SMEM as scalar-prefetch arguments
    (``pltpu.PrefetchScalarGridSpec``), available before the body runs so
    they steer the DMA and the masks;
  * grid ``(B, Hkv, q_chunks, pages)`` with the page axis innermost
    ("arbitrary"): each program attends one ``block_q``-token query chunk of
    one row against one KV page; online-softmax state (m, l, acc) for the
    chunk's ``block_q x G`` queries (G = Hq/Hkv heads sharing a KV head)
    lives in VMEM scratch and is carried across pages;
  * per-row causal masks are *offset by the cached-prefix length*: query j
    of row b sits at global position ``starts[b] + j`` and attends keys
    ``[0, starts[b] + j]`` — so a row reuses its cached prefix KV without
    recomputing it;
  * pages wholly above the chunk's causal diagonal, wholly below its
    sliding window, or past the row's written length are skipped via
    ``pl.when`` — bucket-padding rows and padded query chunks cost at most
    one masked page;
  * sliding-window and logit-softcap masking match ``paged_attention``.

The suffix K/V themselves are written into their pages by the surrounding
jit (``models/attention.py`` scatters row b's L new tokens at positions
``starts[b] + j`` through the table, the decode write idiom generalized to
L tokens; the page stores are donated, so XLA updates them in place) —
the kernel then reads pages that already contain the new tokens.

``interpret=True`` runs the same program as traced JAX ops on CPU CI;
``chunked_prefill_ref`` is the ``jax.nn`` fallback for backends without
Pallas (the CPU serving default) and the parity oracle in tests. See
``docs/kernels.md`` for the grid/SMEM layout side by side with the decode
kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_attention import gather_pages

NEG_INF = -1e30


def _kernel(tbl_ref, start_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *,
            scale: float, cap: float, window: int,
            bs: int, bq: int, g: int, nb: int):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    i = pl.program_id(3)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = start_ref[b]
    total = start + len_ref[b]             # row's written length (prefix+suffix)
    q_lo = start + qi * bq                 # global position of chunk's first query
    live = q_lo < total                    # chunk holds at least one valid query
    live &= i * bs < total                 # page not past the written length
    live &= i * bs <= q_lo + bq - 1        # page not wholly above the diagonal
    if window > 0:                         # page not wholly below the window
        live &= (i + 1) * bs > q_lo + 1 - window

    @pl.when(live)
    def _compute():
        k = k_ref[0, 0]                    # (bs, hd)
        v = v_ref[0, 0]
        shape = (bq, bs)
        iq = q_lo + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        ok = iq < total                    # padded queries (j >= lens) -> 0 rows
        ik = i * bs + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        ok &= ik <= iq                     # causal, offset by the cached prefix
        if window > 0:
            ok &= (iq - ik) < window
        # one (bq, hd) x (hd, bs) product per query head sharing this KV
        # head: folding G into the row axis would need a sublane reshape
        # Mosaic refuses for G = 3
        for h in range(g):
            s = jnp.dot(q_ref[0, 0, h], k.T,
                        preferred_element_type=jnp.float32) * scale
            if cap > 0:
                s = cap * jnp.tanh(s / cap)
            s = jnp.where(ok, s, NEG_INF)
            m_prev = m_ref[h]              # (bq, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # fully-masked query rows keep m == NEG_INF; shift the exponent
            # so they contribute p = 0 (exp(NEG_INF - NEG_INF) would be 1)
            p = jnp.exp(s - jnp.maximum(m_new, NEG_INF / 2))
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(i == nb - 1)
    def _finalize():
        # rows that attended nothing (query padding, zero-length rows)
        # finalize with l == 0 -> output 0
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "cap", "window",
                                             "block_q", "interpret"))
def chunked_prefill(q, k_pages, v_pages, block_tables, starts, lens, *,
                    scale=None, cap: float = 0.0, window: int = 0,
                    block_q: int = 16, interpret: bool = False):
    """Batched suffix-prefill attention over a paged KV cache.

    q: (B, L, Hq, hd) — each row's suffix queries, rotary already applied,
      right-padded to the shared length bucket ``L``.
    k_pages/v_pages: (num_blocks, Hkv, bs, hd) — the shared head-major page
      stores, already holding the new suffix K/V (the caller scatters them in).
    block_tables: (B, nb) int32 — physical page ids per request, ragged rows
      padded with the trash page (0).
    starts: (B,) int32 — cached-prefix length per row (the global position
      of its first suffix query).
    lens: (B,) int32 — valid suffix tokens per row; query rows past
      ``lens[b]`` (bucket padding) return zeros, as do rows with
      ``lens[b] == 0``.

    Returns (B, L, Hq, hd) in q.dtype.
    """
    b, lq, hq, hd = q.shape
    nb_total, hkv, bs, _ = k_pages.shape
    g = hq // hkv
    nb = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    bq = min(block_q, lq)
    pad = (-lq) % bq
    if pad:
        # padded queries sit at global positions >= starts + lens, so the
        # validity mask zeroes them without any extra bookkeeping
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nq = (lq + pad) // bq
    # head-major queries (B, Hkv, G, L, hd): each block's minor dims are a
    # (bq, hd) tile of one query head
    qg = q.reshape(b, nq * bq, hkv, g, hd).transpose(0, 2, 3, 1, 4)
    tables = block_tables.astype(jnp.int32)
    st = starts.astype(jnp.int32)
    ln = lens.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,             # tables, starts, lens -> SMEM
        grid=(b, hkv, nq, nb),
        in_specs=[
            pl.BlockSpec((1, 1, g, bq, hd),
                         lambda bi, h, qi, i, tbl, s, ln: (bi, h, 0, qi, 0)),
            pl.BlockSpec((1, 1, bs, hd),
                         lambda bi, h, qi, i, tbl, s, ln: (tbl[bi, i], h, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd),
                         lambda bi, h, qi, i, tbl, s, ln: (tbl[bi, i], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, bq, hd),
                               lambda bi, h, qi, i, tbl, s, ln: (bi, h, 0, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, bq, 1), jnp.float32),   # running max m
            pltpu.VMEM((g, bq, 1), jnp.float32),   # running denom l
            pltpu.VMEM((g, bq, hd), jnp.float32),  # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, cap=cap, window=window,
                          bs=bs, bq=bq, g=g, nb=nb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, nq * bq, hd), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
    )(tables, st, ln, qg, k_pages, v_pages)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, nq * bq, hq, hd)
    return out[:, :lq]


def chunked_prefill_ref(q, k_pages, v_pages, block_tables, starts, lens, *,
                        scale=None, cap: float = 0.0, window: int = 0):
    """``jax.nn`` fallback for backends without Pallas, and the test oracle.

    Gathers only the pages named by the block tables (O(tokens attended),
    inside the surrounding jit) and runs a masked softmax in fp32 with the
    same per-row prefix-offset causal semantics as the kernel.
    """
    b, lq, hq, hd = q.shape
    hkv, bs = k_pages.shape[1], k_pages.shape[2]
    g = hq // hkv
    nb = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    k = gather_pages(k_pages, block_tables)
    v = gather_pages(v_pages, block_tables)
    qg = q.reshape(b, lq, hkv, g, hd)
    s = jnp.einsum("blkgd,bskd->bkgls", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if cap > 0:
        s = cap * jnp.tanh(s / cap)
    iq = starts[:, None] + jnp.arange(lq)              # (B, L) global positions
    ik = jnp.arange(nb * bs)
    ok = iq[..., None] < (starts + lens)[:, None, None]  # mask padded queries
    ok &= ik[None, None] <= iq[..., None]                # prefix-offset causal
    if window > 0:
        ok &= (iq[..., None] - ik[None, None]) < window
    s = jnp.where(ok[:, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jnp.maximum(m, NEG_INF / 2))       # all-masked rows -> ~0
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bkgls,bskd->blkgd", p / jnp.maximum(l, 1e-30),
                   v.astype(jnp.float32))
    return o.reshape(b, lq, hq, hd).astype(q.dtype)
