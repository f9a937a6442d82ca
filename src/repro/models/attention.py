"""Attention variants: GQA, local/global (gemma2), MLA (deepseek-v2), cross.

All paths support three execution modes:
  * dense      — one einsum, short sequences
  * chunked    — online-softmax scan over KV (and Q) blocks; O(T) memory,
                 used for 32k prefill and as the portable oracle for the
                 Pallas flash kernel
  * pallas     — kernels/flash_attention.py on TPU (interpret=True on CPU)

KV caches are explicit pytrees; decode writes one position via
``dynamic_update_slice`` and attends under a positional mask.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ParallelCtx, apply_rope, softcap, dense_init, split_key

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Param init
# ---------------------------------------------------------------------------

def gqa_init(key, cfg, dtype=jnp.float32):
    d, hd = cfg.d_model, cfg.head_dim
    kq, kk, kv, ko = split_key(key, 4)
    return {
        "wq": {"w": dense_init(kq, d, cfg.n_heads * hd, dtype)},
        "wk": {"w": dense_init(kk, d, cfg.n_kv_heads * hd, dtype)},
        "wv": {"w": dense_init(kv, d, cfg.n_kv_heads * hd, dtype)},
        "wo": {"w": dense_init(ko, cfg.n_heads * hd, d, dtype)},
    }


def mla_init(key, cfg, dtype=jnp.float32):
    d = cfg.d_model
    h, dn, dr, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kl = cfg.kv_lora_rank
    ks = split_key(key, 6)
    return {
        "wq": {"w": dense_init(ks[0], d, h * (dn + dr), dtype)},
        "w_dkv": {"w": dense_init(ks[1], d, kl, dtype)},
        "w_krope": {"w": dense_init(ks[2], d, dr, dtype)},
        "w_uk": dense_init(ks[3], kl, h * dn, dtype),     # raw: used via einsum
        "w_uv": dense_init(ks[4], kl, h * dv, dtype),
        "wo": {"w": dense_init(ks[5], h * dv, d, dtype)},
    }


def cross_attn_init(key, cfg, dtype=jnp.float32):
    return gqa_init(key, cfg, dtype)


# ---------------------------------------------------------------------------
# Core scaled-dot-product attention with GQA grouping
# ---------------------------------------------------------------------------

def _mask_bias(iq, ik, *, causal: bool, window: int):
    """Additive bias from global position indices.

    iq: (len_q,) shared positions, or (B, len_q) per-request positions (the
    continuous-batching engine decodes requests at different offsets in one
    step). Returns (len_q, len_k) resp. (B, len_q, len_k)."""
    d = iq[..., None] - ik
    ok = jnp.ones(d.shape, bool)
    if causal:
        ok &= d >= 0
    if window > 0:
        ok &= d < window
    return jnp.where(ok, 0.0, NEG_INF)


def _q_positions(q_offset, tq):
    """Global query positions; q_offset scalar or (B,) -> (tq,) or (B, tq)."""
    off = jnp.asarray(q_offset)
    if off.ndim == 1:
        return off[:, None] + jnp.arange(tq)
    return off + jnp.arange(tq)


def _add_bias(s, bias):
    """s: (b, hkv, g, tq, tk); bias (tq, tk) or (b, tq, tk)."""
    if bias.ndim == 3:
        return s + bias[:, None, None]
    return s + bias


def _row_update(cache_arr, update, pos):
    """Write one token per batch row at per-row positions.

    cache_arr: (B, L, ...); update: (B, 1, ...); pos: (B,) int32."""
    start = (0,) * (cache_arr.ndim - 2)
    return jax.vmap(
        lambda c, u, p: jax.lax.dynamic_update_slice(c, u, (p,) + start)
    )(cache_arr, update, pos)


def _dense_sdpa(q, k, v, *, q_offset, causal, window, cap, scale):
    b, tq, hq, hd = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, tq, hkv, g, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32) * scale
    s = softcap(s, cap)
    iq = _q_positions(q_offset, tq)
    ik = jnp.arange(tk)
    s = _add_bias(s, _mask_bias(iq, ik, causal=causal, window=window))
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v)
    return o.reshape(b, tq, hq, hd)


def _chunked_sdpa(q, k, v, *, q_offset, causal, window, cap, scale,
                  chunk_q: int, chunk_kv: int, skip_masked_blocks: bool = False):
    """FlashAttention-style two-level scan; O(chunk² ) score memory."""
    b, tq, hq, hd = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    cq = min(chunk_q, tq)
    ck = min(chunk_kv, tk)
    # ragged lengths: pad (padded KV keys are masked out via kv_valid; padded
    # queries are sliced off the output) — keeps memory O(chunk²) for shapes
    # like whisper's 1500-frame cross attention
    pad_q = (-tq) % cq
    pad_k = (-tk) % ck
    kv_valid = tk
    if pad_q or pad_k:
        if pad_q:
            q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        if pad_k:
            k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        tq_p, tk_p = tq + pad_q, tk + pad_k
    else:
        tq_p, tk_p = tq, tk
    out = _chunked_sdpa_padded(q, k, v, q_offset=q_offset, causal=causal,
                               window=window, cap=cap, scale=scale,
                               cq=cq, ck=ck, kv_valid=kv_valid)
    return out[:, :tq]


def _chunked_sdpa_padded(q, k, v, *, q_offset, causal, window, cap, scale,
                         cq, ck, kv_valid):
    b, tq, hq, hd = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    nq, nk = tq // cq, tk // ck
    qg = q.reshape(b, nq, cq, hkv, g, hd)
    kc = k.reshape(b, nk, ck, hkv, hd)
    vc = v.reshape(b, nk, ck, hkv, hd)

    def one_q_chunk(qi, q_blk):
        iq = _q_positions(q_offset, cq) + qi * cq
        m0 = jnp.full((b, hkv, g, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, cq), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, cq, hd), jnp.float32)

        def kv_body(carry, inp):
            m, l, acc = carry
            ki, k_blk, v_blk = inp
            ik = ki * ck + jnp.arange(ck)
            s = jnp.einsum("bqkgd,bskd->bkgqs", q_blk, k_blk)
            s = s.astype(jnp.float32) * scale
            s = softcap(s, cap)
            s = _add_bias(s, _mask_bias(iq, ik, causal=causal, window=window))
            s = jnp.where((ik < kv_valid)[None, None, None, None, :],
                          s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p.astype(v_blk.dtype), v_blk)
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = jax.lax.scan(
            kv_body, (m0, l0, a0),
            (jnp.arange(nk), jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0)))
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.moveaxis(o, 3, 1).reshape(b, cq, hq, hd)   # (b,hkv,g,cq,hd)->(b,cq,hq,hd)

    def q_body(_, inp):
        qi, q_blk = inp
        return None, one_q_chunk(qi, q_blk)

    _, outs = jax.lax.scan(q_body, None,
                           (jnp.arange(nq), jnp.moveaxis(qg, 1, 0)))
    return jnp.moveaxis(outs, 0, 1).reshape(b, tq, hq, hd).astype(q.dtype)


def sdpa(q, k, v, *, ctx: ParallelCtx, q_offset=0, causal=True, window=0,
         cap=0.0, scale=None):
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    long_seq = max(q.shape[1], k.shape[1]) > ctx.dense_attn_max_seq
    if (ctx.use_pallas and causal and q.shape[1] == k.shape[1]
            and window == 0 and jnp.ndim(q_offset) == 0):
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, scale=scale, cap=cap)
    if long_seq:
        return _chunked_sdpa(q, k, v, q_offset=q_offset, causal=causal,
                             window=window, cap=cap, scale=scale,
                             chunk_q=ctx.attn_chunk_q, chunk_kv=ctx.attn_chunk_kv)
    return _dense_sdpa(q, k, v, q_offset=q_offset, causal=causal,
                       window=window, cap=cap, scale=scale)


# ---------------------------------------------------------------------------
# GQA layer (with optional local window, softcap, rope, KV cache)
# ---------------------------------------------------------------------------

def gqa_empty_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    hd = cfg.head_dim
    return {"k": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype),
            "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype)}


def gqa_apply(cfg, params, x, *, ctx: ParallelCtx, cos_sin=None,
              cache=None, pos=None, local: bool = False,
              causal: bool = True, paged_tables=None,
              lens=None) -> Tuple[jax.Array, Optional[dict]]:
    from repro.models.linear import linear_apply
    b, t, _ = x.shape
    hd = cfg.head_dim
    q = linear_apply(params["wq"], x).reshape(b, t, cfg.n_heads, hd)
    k = linear_apply(params["wk"], x).reshape(b, t, cfg.n_kv_heads, hd)
    v = linear_apply(params["wv"], x).reshape(b, t, cfg.n_kv_heads, hd)
    if cos_sin is not None:
        cos, sin = cos_sin
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    window = cfg.local_window if local else 0
    scale = cfg.query_scale if cfg.query_scale > 0 else None
    new_cache = None
    if paged_tables is not None:
        # paged serving: the cache leaves are the pool's head-major page
        # stores (num_blocks, hkv, block_size, hd); write the new K/V
        # straight into their pages and attend through the block-table
        # indirection — no contiguous copy of the KV history is ever
        # materialized. t == 1 is a decode step; t > 1 is a chunked suffix
        # prefill writing row i's L tokens at positions pos[i] + j (padded
        # tail tokens past lens[i] land in the row's last partial page or
        # the trash page, hidden by the causal masks until a later decode
        # overwrites them).
        assert pos is not None and jnp.ndim(pos) == 1, \
            "paged path needs per-request positions"
        from repro.kernels import ops as kops
        bs = cache["k"].shape[2]
        p = pos[:, None] + jnp.arange(t)                 # (B, t) positions
        blk = jnp.take_along_axis(paged_tables, p // bs, axis=1)
        # (blk, :, slot) indexes (B, t) x hkv -> updates shaped (B, t, hkv, hd)
        kf = cache["k"].at[blk, :, p % bs].set(k.astype(cache["k"].dtype))
        vf = cache["v"].at[blk, :, p % bs].set(v.astype(cache["v"].dtype))
        if t == 1:
            o = kops.paged_attention(
                q[:, 0], kf, vf, paged_tables, pos + 1, scale=scale,
                cap=cfg.attn_logit_softcap, window=window,
                impl=ctx.paged_attn_impl)[:, None].astype(q.dtype)
        else:
            assert lens is not None, "chunked paged prefill needs lens"
            o = kops.chunked_prefill(
                q, kf, vf, paged_tables, pos, lens, scale=scale,
                cap=cfg.attn_logit_softcap, window=window,
                impl=ctx.paged_attn_impl).astype(q.dtype)
        y = linear_apply(params["wo"], o.reshape(b, t, cfg.n_heads * hd))
        return y, {"k": kf, "v": vf}
    if cache is not None:
        if pos is None:                                   # prefill: fill [0, t)
            kf = cache["k"].at[:, :t].set(k.astype(cache["k"].dtype))
            vf = cache["v"].at[:, :t].set(v.astype(cache["v"].dtype))
            new_cache = {"k": kf, "v": vf}
            o = sdpa(q, k, v, ctx=ctx, q_offset=0, causal=causal,
                     window=window, cap=cfg.attn_logit_softcap, scale=scale)
        else:                                             # decode: one token
            if jnp.ndim(pos):                             # per-request positions
                kf = _row_update(cache["k"], k.astype(cache["k"].dtype), pos)
                vf = _row_update(cache["v"], v.astype(cache["v"].dtype), pos)
            else:
                kf = jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), (0, pos, 0, 0))
                vf = jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), (0, pos, 0, 0))
            new_cache = {"k": kf, "v": vf}
            o = sdpa(q, kf.astype(q.dtype), vf.astype(q.dtype), ctx=ctx,
                     q_offset=pos, causal=causal, window=window,
                     cap=cfg.attn_logit_softcap, scale=scale)
    else:
        o = sdpa(q, k, v, ctx=ctx, q_offset=0, causal=causal,
                 window=window, cap=cfg.attn_logit_softcap, scale=scale)
    y = linear_apply(params["wo"], o.reshape(b, t, cfg.n_heads * hd))
    return y, new_cache


# ---------------------------------------------------------------------------
# MLA layer (deepseek-v2-lite)
# ---------------------------------------------------------------------------

def mla_empty_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    return {"c": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype)}


def mla_apply(cfg, params, x, *, ctx: ParallelCtx, cos_sin=None,
              cache=None, pos=None, **_) -> Tuple[jax.Array, Optional[dict]]:
    from repro.models.linear import linear_apply
    b, t, _ = x.shape
    h, dn, dr, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    scale = 1.0 / ((dn + dr) ** 0.5)
    q = linear_apply(params["wq"], x).reshape(b, t, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    c = linear_apply(params["w_dkv"], x)                       # (b, t, kl)
    k_rope = linear_apply(params["w_krope"], x)[:, :, None, :]  # (b, t, 1, dr)
    if cos_sin is not None:
        cos, sin = cos_sin
        q_rope = apply_rope(q_rope, cos, sin)
        k_rope = apply_rope(k_rope, cos, sin)
    k_rope = k_rope[:, :, 0, :]
    w_uk = params["w_uk"].astype(x.dtype).reshape(cfg.kv_lora_rank, h, dn)
    w_uv = params["w_uv"].astype(x.dtype).reshape(cfg.kv_lora_rank, h, dv)

    if cache is not None and pos is not None:
        # absorbed decode: score in latent space, never materialize per-head K/V
        if jnp.ndim(pos):                                  # per-request positions
            cf = _row_update(cache["c"], c.astype(cache["c"].dtype), pos)
            rf = _row_update(cache["k_rope"],
                             k_rope.astype(cache["k_rope"].dtype), pos)
        else:
            cf = jax.lax.dynamic_update_slice(
                cache["c"], c.astype(cache["c"].dtype), (0, pos, 0))
            rf = jax.lax.dynamic_update_slice(
                cache["k_rope"], k_rope.astype(cache["k_rope"].dtype),
                (0, pos, 0))
        new_cache = {"c": cf, "k_rope": rf}
        q_c = jnp.einsum("bthd,khd->bthk", q_nope, w_uk)       # (b,1,h,kl)
        s = (jnp.einsum("bthk,bsk->bhts", q_c, cf.astype(x.dtype)) +
             jnp.einsum("bthd,bsd->bhts", q_rope, rf.astype(x.dtype)))
        s = s.astype(jnp.float32) * scale
        iq = _q_positions(pos, t)
        ik = jnp.arange(cf.shape[1])
        bias = _mask_bias(iq, ik, causal=True, window=0)
        s = s + (bias[:, None] if bias.ndim == 3 else bias[None, None])
        p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        ctx_c = jnp.einsum("bhts,bsk->bthk", p, cf.astype(x.dtype))
        o = jnp.einsum("bthk,khd->bthd", ctx_c, w_uv)          # (b,t,h,dv)
    else:
        # train/prefill: expand K/V (MHA after expansion)
        k_nope = jnp.einsum("btk,khd->bthd", c, w_uk)
        v = jnp.einsum("btk,khd->bthd", c, w_uv)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, t, h, dr))], -1)
        qq = jnp.concatenate([q_nope, q_rope], -1)
        if dv < dn + dr:                                       # pad V to head dim
            v_p = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dn + dr - dv)))
        else:
            v_p = v
        o = sdpa(qq, k, v_p, ctx=ctx, q_offset=0, causal=True, scale=scale)
        o = o[..., :dv]
        new_cache = None
        if cache is not None:                                  # prefill fills cache
            cf = cache["c"].at[:, :t].set(c.astype(cache["c"].dtype))
            rf = cache["k_rope"].at[:, :t].set(k_rope.astype(cache["k_rope"].dtype))
            new_cache = {"c": cf, "k_rope": rf}
    y = linear_apply(params["wo"], o.reshape(b, t, h * dv))
    return y, new_cache


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder)
# ---------------------------------------------------------------------------

def cross_cache_from_encoder(cfg, params, enc_out, dtype=jnp.bfloat16):
    """Precompute K/V over encoder states once per request."""
    from repro.models.linear import linear_apply
    b, s, _ = enc_out.shape
    hd = cfg.head_dim
    k = linear_apply(params["wk"], enc_out).reshape(b, s, cfg.n_kv_heads, hd)
    v = linear_apply(params["wv"], enc_out).reshape(b, s, cfg.n_kv_heads, hd)
    return {"ck": k.astype(dtype), "cv": v.astype(dtype)}


def cross_attn_apply(cfg, params, x, *, ctx: ParallelCtx, enc_out=None,
                     cross_cache=None) -> jax.Array:
    from repro.models.linear import linear_apply
    b, t, _ = x.shape
    hd = cfg.head_dim
    q = linear_apply(params["wq"], x).reshape(b, t, cfg.n_heads, hd)
    if cross_cache is not None:
        k = cross_cache["ck"].astype(q.dtype)
        v = cross_cache["cv"].astype(q.dtype)
    else:
        s = enc_out.shape[1]
        k = linear_apply(params["wk"], enc_out).reshape(b, s, cfg.n_kv_heads, hd)
        v = linear_apply(params["wv"], enc_out).reshape(b, s, cfg.n_kv_heads, hd)
    o = sdpa(q, k, v, ctx=ctx, q_offset=0, causal=False)
    return linear_apply(params["wo"], o.reshape(b, t, cfg.n_heads * hd))
