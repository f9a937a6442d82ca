"""Blocked Gram accumulation Pallas kernel:  G = Σ_k A[k]ᵀ A[k].

The memory-bounded Gram path the paper's baselines (SVD-LLM / SVD-LLM v2)
rely on: activations stream through in token chunks and the n×n Gram matrix
accumulates in fp32. On TPU this is a K-reduction matmul: grid
(n/bi, n/bj, K/bk) with the output block revisited across the k dimension
("arbitrary" semantics) and initialized at k == 0.

VMEM per program (bi=bj=256, bk=512, bf16 in / fp32 acc):
  a_i 0.25MB + a_j 0.25MB + acc 0.25MB ≈ 0.75MB — deliberately small so many
programs can overlap DMA with MXU work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(ai_ref, aj_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(ai_ref[...].T, aj_ref[...],
                          preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("block_i", "block_j", "block_k", "interpret"))
def gram_accum(a, *, block_i: int = 256, block_j: int = 256,
               block_k: int = 512, interpret: bool = False):
    """a: (k_tokens, n) chunk of Xᵀ -> (n, n) fp32 Gram contribution aᵀa."""
    k_tokens, n = a.shape
    bi = min(block_i, n)
    bj = min(block_j, n)
    bk = min(block_k, k_tokens)
    if n % bi or n % bj or k_tokens % bk:
        return a.T.astype(jnp.float32) @ a.astype(jnp.float32)
    grid = (n // bi, n // bj, k_tokens // bk)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bi), lambda i, j, k: (k, i)),
            pl.BlockSpec((bk, bj), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(a, a)
