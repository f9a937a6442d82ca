"""Causal FlashAttention-2 Pallas kernel with GQA and logit softcap.

Grid: (B·Hq, Tq/bq, Tk/bk); the KV axis is the innermost ("arbitrary")
dimension so the online-softmax state (m, l, acc) lives in VMEM scratch and
is carried across KV blocks. GQA is expressed in the BlockSpec index maps:
the K/V block index maps a query head h to its KV head h // (Hq // Hkv), so
K/V HBM traffic scales with Hkv, not Hq.

Block-causal skip: KV blocks strictly above the diagonal are never computed
(``pl.when``), so FLOPs match the true causal half, unlike the masked dense
path.

VMEM per program (bq=bk=128, hd=128, bf16): q/k/v 32KB·3 + acc fp32 64KB +
m/l 1KB ≈ 160KB — deliberately small so many programs overlap DMA with MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, cap: float, bq: int, bk: int, nk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ki * bk <= qi * bq + bq - 1)          # skip fully-masked blocks
    def _compute():
        q = q_ref[0]                               # (bq, hd)
        k = k_ref[0]                               # (bk, hd)
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if cap > 0:
            s = cap * jnp.tanh(s / cap)
        iq = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        ik = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(iq >= ik, s, NEG_INF)
        m_prev = m_ref[...]                        # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "cap", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, scale=None, cap: float = 0.0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B, T, Hq, hd); k/v: (B, T, Hkv, hd); causal. Returns (B, T, Hq, hd)."""
    b, t, hq, hd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    bq = min(block_q, t)
    bk = min(block_k, t)
    if t % bq or t % bk:
        from repro.kernels.ref import flash_attention_ref
        return flash_attention_ref(q, k, v, scale=scale, cap=cap)
    nq, nk = t // bq, t // bk
    qh = q.transpose(0, 2, 1, 3).reshape(b * hq, t, hd)
    kh = k.transpose(0, 2, 1, 3).reshape(b * hkv, t, hd)
    vh = v.transpose(0, 2, 1, 3).reshape(b * hkv, t, hd)

    def kv_index(bh, qi, ki):
        return ((bh // hq) * hkv + (bh % hq) // g, ki, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, cap=cap, bq=bq, bk=bk, nk=nk),
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, hd), kv_index),
            pl.BlockSpec((1, bk, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, t, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),       # running max m
            pltpu.VMEM((bq, 1), jnp.float32),       # running denom l
            pltpu.VMEM((bq, hd), jnp.float32),      # output accumulator
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qh, kh, vh)
    return out.reshape(b, hq, t, hd).transpose(0, 2, 1, 3)
