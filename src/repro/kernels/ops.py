"""Public jit'd wrappers for the Pallas kernels.

On the CPU container the kernels execute in ``interpret=True`` mode (the
kernel body runs as traced JAX ops — bit-for-bit the same program the Mosaic
compiler would lower on TPU). On TPU they compile natively.
"""
from __future__ import annotations

import jax

from repro.kernels import chunked_prefill as _cp
from repro.kernels import flash_attention as _fa
from repro.kernels import gram_accum as _ga
from repro.kernels import lowrank_linear as _ll
from repro.kernels import paged_attention as _pa


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def lowrank_linear(x, b_t, a_t, **kw):
    kw.setdefault("interpret", _interpret())
    return _ll.lowrank_linear(x, b_t, a_t, **kw)


def gram_accum(a, **kw):
    kw.setdefault("interpret", _interpret())
    return _ga.gram_accum(a, **kw)


def flash_attention(q, k, v, **kw):
    kw.setdefault("interpret", _interpret())
    return _fa.flash_attention(q, k, v, **kw)


def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    impl=None, **kw):
    """Paged-attention decode dispatch.

    impl: None/"auto" — native Pallas on TPU, ``jax.nn`` reference
    elsewhere (interpret mode is far too slow for a per-step hot path);
    "pallas" — force the kernel (native on TPU, interpret elsewhere, used
    by CI parity tests); "ref" — force the jax.nn fallback.
    """
    if impl in (None, "auto"):
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl == "ref":
        return _pa.paged_attention_ref(q, k_pages, v_pages, block_tables,
                                       lengths, **kw)
    assert impl == "pallas", f"unknown paged-attention impl: {impl}"
    return _pa.paged_attention(q, k_pages, v_pages, block_tables, lengths,
                               interpret=_interpret(), **kw)


def chunked_prefill(q, k_pages, v_pages, block_tables, starts, lens, *,
                    impl=None, **kw):
    """Chunked-prefill (batched paged suffix prefill) dispatch.

    Same policy as ``paged_attention``: impl None/"auto" — native Pallas on
    TPU, ``jax.nn`` reference elsewhere (interpret mode is far too slow for
    a hot path); "pallas" — force the kernel (native on TPU, interpret
    elsewhere, used by CI parity tests); "ref" — force the jax.nn fallback.
    """
    if impl in (None, "auto"):
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl == "ref":
        return _cp.chunked_prefill_ref(q, k_pages, v_pages, block_tables,
                                       starts, lens, **kw)
    assert impl == "pallas", f"unknown chunked-prefill impl: {impl}"
    return _cp.chunked_prefill(q, k_pages, v_pages, block_tables, starts,
                               lens, interpret=_interpret(), **kw)
