"""Decoder-only LM assembly: scan over super-blocks, caches, chunked loss.

The repeating layer pattern of every architecture (dense, gemma2 local/global
pairs, deepseek first-dense-then-MoE, jamba 1:7 attn:mamba with interleaved
MoE, xlstm sLSTM/mLSTM mix) is expressed as a ``prefix`` of unrolled layers
plus a ``period`` scanned ``n_rep`` times over stacked params — one compiled
block body regardless of depth, which keeps HLO size and compile time flat.

Loss never materializes (B, T, vocab) logits: a scan over sequence chunks
computes partial cross-entropy against the (possibly vocab-sharded) LM head.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import attention as attn
from repro.models import ffn as ffn_lib
from repro.models import ssm as ssm_lib
from repro.models import xlstm as xlstm_lib
from repro.models.common import (CPU_CTX, ParallelCtx, constrain_act, make_norm,
                                 mrope_cos_sin, rope_cos_sin, softcap,
                                 dense_init, split_key)
from repro.models.linear import linear_apply
from repro.obs import trace


def chunked_ce(h, targets, head_w, *, transform=None, chunk: int = 512):
    """Cross-entropy without materializing (B, T, vocab) logits.

    Scans over sequence chunks (padding + masking the tail so any T works);
    each chunk computes its logits against the (possibly vocab-sharded) head
    and reduces to scalars immediately.
    """
    b, t, d = h.shape
    ck = min(chunk, t)
    pad = (-t) % ck
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
    mask = (jnp.arange(t + pad) < t).astype(jnp.float32)   # (T+pad,)
    nck = (t + pad) // ck

    def chunk_body(carry, xs):
        tot, cnt = carry
        h_c, y_c, m_c = xs                               # (B,ck,d) (B,ck) (ck,)
        logits = (h_c @ head_w.astype(h_c.dtype)).astype(jnp.float32)
        if transform is not None:
            logits = transform(logits)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y_c[..., None], axis=-1)[..., 0]
        tot = tot + jnp.sum((lse - gold) * m_c[None, :])
        cnt = cnt + b * jnp.sum(m_c)
        return (tot, cnt), None

    h_r = h.reshape(b, nck, ck, d).swapaxes(0, 1)
    y_r = targets.reshape(b, nck, ck).swapaxes(0, 1)
    m_r = mask.reshape(nck, ck)
    (tot, cnt), _ = jax.lax.scan(
        chunk_body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (h_r, y_r, m_r))
    return tot / cnt


@dataclasses.dataclass(frozen=True)
class SubSpec:
    kind: str          # attn | mamba | mlstm | slstm
    is_moe: bool
    is_local: bool


def period_specs(cfg: ModelConfig):
    """(prefix_specs, period_specs, n_rep). Pattern must be periodic."""
    n = cfg.n_layers

    def spec(i):
        return SubSpec(cfg.layer_kind(i), cfg.layer_is_moe(i),
                       cfg.layer_is_local_attn(i))

    prefix = list(range(cfg.first_k_dense))
    rest = n - len(prefix)
    # period length: lcm of the pattern generators present
    p = 1
    if cfg.local_window > 0:
        p = max(p, 2)
    if cfg.attn_every:
        p = max(p, cfg.attn_every)
    if cfg.uses_moe and cfg.moe_every > 1:
        p = max(p, cfg.moe_every)
    if cfg.family == "ssm" and cfg.xlstm.slstm_every:
        p = max(p, cfg.xlstm.slstm_every)
    while rest % p:
        p += 1                      # fall back to a longer period that divides
    base = len(prefix)
    # verify periodicity
    for i in range(base, n):
        a, b = spec(i), spec(base + (i - base) % p)
        assert a == b, f"layer pattern not periodic: layer {i} {a} != {b}"
    return ([spec(i) for i in range(base)],
            [spec(base + j) for j in range(p)], rest // p)


# ---------------------------------------------------------------------------
# Per-sublayer init / apply
# ---------------------------------------------------------------------------

_MIXER_INIT = {
    "attn": lambda key, cfg, dt: (attn.mla_init(key, cfg, dt)
                                  if cfg.kv_lora_rank else attn.gqa_init(key, cfg, dt)),
    "mamba": ssm_lib.mamba_init,
    "mlstm": xlstm_lib.mlstm_init,
    "slstm": xlstm_lib.slstm_init,
}

_MIXER_APPLY = {
    "attn": lambda cfg, p, x, **kw: (attn.mla_apply(cfg, p, x, **kw)
                                     if cfg.kv_lora_rank
                                     else attn.gqa_apply(cfg, p, x, **kw)),
    "mamba": ssm_lib.mamba_apply,
    "mlstm": xlstm_lib.mlstm_apply,
    "slstm": xlstm_lib.slstm_apply,
}


def _has_ffn(cfg, spec: SubSpec) -> bool:
    return cfg.family != "ssm"      # xlstm blocks carry their own projections


def block_init(key, cfg: ModelConfig, spec: SubSpec, dtype=jnp.float32):
    norm_init, _ = make_norm(cfg)
    ks = split_key(key, 4)
    p: Dict[str, Any] = {"norm1": norm_init(),
                         "mixer": _MIXER_INIT[spec.kind](ks[0], cfg, dtype)}
    if _has_ffn(cfg, spec):
        p["norm2"] = norm_init()
        if spec.is_moe:
            p["ffn"] = ffn_lib.moe_init(ks[1], cfg, dtype)
        else:
            p["ffn"] = ffn_lib.mlp_init(ks[1], cfg.d_model, cfg.d_ff,
                                        glu=cfg.family != "encdec", dtype=dtype)
    if cfg.post_block_norm:
        p["post1"] = norm_init()
        if _has_ffn(cfg, spec):
            p["post2"] = norm_init()
    return p


def block_apply(cfg, spec: SubSpec, params, x, *, ctx: ParallelCtx,
                cos_sin, cache=None, pos=None, paged_tables=None,
                lens=None):
    """Returns (x, aux, new_cache)."""
    _, norm = make_norm(cfg)
    res_scale = (cfg.scale_depth / math.sqrt(cfg.n_layers)
                 if cfg.scale_depth else 1.0)
    aux = jnp.zeros((), jnp.float32)

    mixer_kw = dict(ctx=ctx, cache=None if cache is None else cache.get("mixer"),
                    pos=pos)
    if spec.kind == "attn":
        mixer_kw.update(cos_sin=cos_sin, local=spec.is_local,
                        paged_tables=paged_tables, lens=lens)
    h, new_mixer_cache = _MIXER_APPLY[spec.kind](
        cfg, params["mixer"], norm(params["norm1"], x), **mixer_kw)
    if cfg.post_block_norm:
        h = norm(params["post1"], h)
    x = x + res_scale * h

    if _has_ffn(cfg, spec):
        h = norm(params["norm2"], x)
        if spec.is_moe:
            h, aux = ffn_lib.moe_apply(cfg, params["ffn"], h, ctx=ctx)
        else:
            h = ffn_lib.mlp_apply(params["ffn"], h, cfg.act)
        if cfg.post_block_norm:
            h = norm(params["post2"], h)
        x = x + res_scale * h

    new_cache = None
    if cache is not None:
        new_cache = {"mixer": new_mixer_cache if new_mixer_cache is not None
                     else cache.get("mixer")}
    return x, aux, new_cache


def _block_cache(cfg, spec: SubSpec, batch: int, max_len: int, dtype):
    if spec.kind == "attn":
        if cfg.kv_lora_rank:
            return {"mixer": attn.mla_empty_cache(cfg, batch, max_len, dtype)}
        return {"mixer": attn.gqa_empty_cache(cfg, batch, max_len, dtype)}
    if spec.kind == "mamba":
        return {"mixer": ssm_lib.mamba_empty_cache(cfg, batch)}
    if spec.kind == "mlstm":
        return {"mixer": xlstm_lib.mlstm_empty_cache(cfg, batch)}
    if spec.kind == "slstm":
        return {"mixer": xlstm_lib.slstm_empty_cache(cfg, batch)}
    raise ValueError(spec.kind)


# ---------------------------------------------------------------------------
# The LM
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LM:
    cfg: ModelConfig

    # ---------------- params ------------------------------------------------
    def init(self, key, dtype=jnp.float32):
        cfg = self.cfg
        prefix, period, n_rep = period_specs(cfg)
        ks = split_key(key, 4 + len(prefix) + len(period) * n_rep)
        params: Dict[str, Any] = {
            "embed": (jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model),
                                        jnp.float32) * 0.02).astype(dtype),
        }
        norm_init, _ = make_norm(cfg)
        params["final_norm"] = norm_init()
        if not cfg.tie_embeddings:
            params["lm_head"] = {"w": dense_init(ks[1], cfg.d_model,
                                                 cfg.vocab_size, dtype)}
        if cfg.family == "vlm" and cfg.n_vision_tokens:
            params["vision_proj"] = {"w": dense_init(ks[2], cfg.d_model,
                                                     cfg.d_model, dtype)}
        ki = 4
        params["prefix"] = []
        for spec in prefix:
            params["prefix"].append(block_init(ks[ki], cfg, spec, dtype))
            ki += 1
        reps = []
        for rep in range(n_rep):
            blk = {}
            for j, spec in enumerate(period):
                blk[f"sub{j}"] = block_init(ks[ki], cfg, spec, dtype)
                ki += 1
            reps.append(blk)
        params["blocks"] = jax.tree.map(lambda *xs: jnp.stack(xs), *reps)
        return params

    # ---------------- caches -----------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        cfg = self.cfg
        prefix, period, n_rep = period_specs(cfg)
        cache = {"prefix": [_block_cache(cfg, s, batch, max_len, dtype)
                            for s in prefix]}
        one = {f"sub{j}": _block_cache(cfg, s, batch, max_len, dtype)
               for j, s in enumerate(period)}
        cache["blocks"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_rep,) + a.shape), one)
        return cache

    # ---------------- embedding & positions ---------------------------------
    def _embed(self, params, tokens, extra_embeds=None):
        cfg = self.cfg
        x = params["embed"][tokens]
        if cfg.scale_emb != 1.0:
            x = x * cfg.scale_emb
        if extra_embeds is not None:                    # vlm: vision prefix
            v = extra_embeds.astype(x.dtype)
            if "vision_proj" in params:
                v = linear_apply(params["vision_proj"], v)
            x = jnp.concatenate([v, x], axis=1)
        return x

    def _cos_sin(self, batch: int, t: int, offset=0):
        cfg = self.cfg
        if cfg.family == "encdec":
            return None
        off = jnp.asarray(offset)
        # offset (B,) -> per-request positions (B, t); scalar -> shared (t,)
        pos = (off[:, None] if off.ndim else off) + jnp.arange(t)
        if cfg.mrope_sections != (0, 0, 0):
            pids = jnp.broadcast_to(pos, (3, batch, t))
            return mrope_cos_sin(pids, cfg.head_dim, cfg.rope_theta,
                                 cfg.mrope_sections)
        hd = cfg.qk_rope_dim if cfg.kv_lora_rank else cfg.head_dim
        return rope_cos_sin(pos, hd, cfg.rope_theta, cfg.yarn)

    # ---------------- backbone ----------------------------------------------
    def _backbone(self, params, x, *, ctx: ParallelCtx, cache=None, pos=None,
                  paged_tables=None, lens=None, remat: str = "none",
                  capture=None):
        cfg = self.cfg
        prefix, period, n_rep = period_specs(cfg)
        b, t = x.shape[0], x.shape[1]
        cos_sin = self._cos_sin(b, t, 0 if pos is None else pos)
        aux_total = jnp.zeros((), jnp.float32)

        new_prefix_caches = []
        for i, spec in enumerate(prefix):
            c = cache["prefix"][i] if cache is not None else None
            lp = params["prefix"][i]
            if capture is not None:
                lp = capture.wrap(lp, f"prefix/{i}")
            x, aux, nc = block_apply(cfg, spec, lp, x,
                                     ctx=ctx, cos_sin=cos_sin, cache=c, pos=pos,
                                     paged_tables=paged_tables, lens=lens)
            aux_total += aux
            new_prefix_caches.append(nc)

        if capture is not None:
            # unrolled-eager path: python loop so activations are concrete
            assert cache is None, "capture runs on the forward path only"
            for r in range(n_rep):
                blk = jax.tree.map(lambda a: a[r], params["blocks"])
                for j, spec in enumerate(period):
                    lp = capture.wrap(blk[f"sub{j}"], f"blocks/{r}/sub{j}")
                    x, a, _ = block_apply(cfg, spec, lp, x, ctx=ctx,
                                          cos_sin=cos_sin)
                    aux_total = aux_total + a
            _, norm = make_norm(cfg)
            return norm(params["final_norm"], x), aux_total, None

        def body(carry, xs):
            x, aux = carry
            blk, blk_cache = xs
            new_caches = {}
            x = constrain_act(x, ctx)      # pin layout at block boundaries
            for j, spec in enumerate(period):
                c = blk_cache[f"sub{j}"] if blk_cache is not None else None
                x, a, nc = block_apply(cfg, spec, blk[f"sub{j}"], x, ctx=ctx,
                                       cos_sin=cos_sin, cache=c, pos=pos,
                                       paged_tables=paged_tables, lens=lens)
                aux = aux + a
                new_caches[f"sub{j}"] = nc
            x = constrain_act(x, ctx)
            return (x, aux), (new_caches if blk_cache is not None else 0)

        if remat == "full":
            body = jax.checkpoint(body,
                                  policy=jax.checkpoint_policies.nothing_saveable)
        elif remat == "dots":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)

        blk_caches = cache["blocks"] if cache is not None else None
        (x, aux_total2), scanned_caches = jax.lax.scan(
            body, (x, aux_total),
            (params["blocks"], blk_caches) if blk_caches is not None
            else (params["blocks"], None))

        _, norm = make_norm(cfg)
        x = norm(params["final_norm"], x)
        new_cache = None
        if cache is not None:
            new_cache = {"prefix": new_prefix_caches, "blocks": scanned_caches}
        return x, aux_total2, new_cache

    # ---------------- heads --------------------------------------------------
    def _head_w(self, params):
        if self.cfg.tie_embeddings:
            return params["embed"].T
        return params["lm_head"]["w"]

    def _logits(self, params, h):
        cfg = self.cfg
        w = self._head_w(params).astype(h.dtype)
        logits = (h @ w).astype(jnp.float32)
        if cfg.dim_model_base:
            logits = logits / (cfg.d_model / cfg.dim_model_base)
        logits = softcap(logits, cfg.final_logit_softcap)
        return logits

    # ---------------- public: train loss ------------------------------------
    def loss(self, params, batch: Dict[str, jax.Array], *,
             ctx: ParallelCtx = CPU_CTX, remat: str = "none",
             loss_chunk: int = 512,
             compute_dtype=jnp.bfloat16) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """batch: tokens (B,T) int32, plus optional vision_embeds.

        Next-token CE; for vlm the vision prefix positions are excluded.
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self._embed(params, tokens, batch.get("vision_embeds"))
        x = x.astype(compute_dtype)
        h, aux, _ = self._backbone(params, x, ctx=ctx, remat=remat)

        n_vis = cfg.n_vision_tokens if cfg.family == "vlm" else 0
        h_text = h[:, n_vis:]
        targets = tokens[:, 1:]                          # predict next token
        h_in = h_text[:, :-1]

        def transform(logits):
            if cfg.dim_model_base:
                logits = logits / (cfg.d_model / cfg.dim_model_base)
            return softcap(logits, cfg.final_logit_softcap)

        ce = chunked_ce(h_in, targets, self._head_w(params),
                        transform=transform, chunk=loss_chunk)
        return ce + aux, {"ce": ce, "aux": aux}

    # ---------------- public: calibration ------------------------------------
    def capture_forward(self, params, batch, calibrator, *,
                        ctx: ParallelCtx = CPU_CTX, compute_dtype=jnp.float32):
        """Unrolled-eager forward that streams every target linear's input
        activations into the calibrator (per-layer R factors, never X).
        Its ``calib.capture`` span holds the eager forward's dispatch and
        that of every fold it triggers."""
        with trace.span("calib.capture"):
            x = self._embed(params, batch["tokens"],
                            batch.get("vision_embeds"))
            x = x.astype(compute_dtype)
            h, _, _ = self._backbone(params, x, ctx=ctx, capture=calibrator)
        return h

    def capture_prefill(self, params, tokens, calibrator, *,
                        ctx: ParallelCtx = CPU_CTX,
                        compute_dtype=jnp.float32):
        """Capture hook on the serving prefill path: one request's token
        stream ``tokens`` (T,) runs the unrolled-eager forward, streaming
        every target linear's input activations into ``calibrator``.

        Causality makes this the exact replay of what serving computed:
        the activation at position p depends only on tokens <= p, so a
        calibrator that records position range [start, T) here sees the
        same rows a live prefill/decode over those positions produced
        (serve/recalibrate.py slices via its ``record`` override)."""
        batch = {"tokens": jnp.asarray(tokens, jnp.int32).reshape(1, -1)}
        return self.capture_forward(params, batch, calibrator, ctx=ctx,
                                    compute_dtype=compute_dtype)

    # ---------------- public: serving ---------------------------------------
    def prefill(self, params, tokens, cache, *, ctx: ParallelCtx = CPU_CTX,
                vision_embeds=None, compute_dtype=jnp.bfloat16):
        x = self._embed(params, tokens, vision_embeds).astype(compute_dtype)
        h, _, cache = self._backbone(params, x, ctx=ctx, cache=cache, pos=None)
        return self._logits(params, h[:, -1:]), cache

    def prefill_chunk(self, params, tokens, cache, pos, lens, *,
                      ctx: ParallelCtx = CPU_CTX, compute_dtype=jnp.bfloat16,
                      block_tables=None):
        """Prefill a batch of suffix chunks at per-request cache offsets.

        tokens: (B, L) int32 — each row is a request's un-cached prompt
        suffix, right-padded to the shared length bucket ``L``; pos: (B,)
        int32 start offsets (the length of the row's cached prefix); lens:
        (B,) int32 valid token counts per row. Rides the same vector-``pos``
        attention path as ``decode_step`` (row-wise cache writes at
        ``pos[i] + j``, per-row causal masks over the whole cache), so a row
        attends to its cached prefix KV without recomputing it. Returns the
        logits at each row's last *valid* token, (B, vocab).

        With ``block_tables`` (B, nb) the cache is the paged view from
        ``BlockPool.paged_cache`` — attention layers scatter the suffix K/V
        into their pages and attend through the table indirection
        (``kernels/chunked_prefill.py``) instead of a gathered contiguous
        cache.

        Padded tail tokens (``j >= lens[i]``) write garbage K/V past the
        row's real length; the causal mask hides those positions until a
        later decode overwrites them, and ``BlockPool.scatter_suffix`` (the
        gather path) never writes blocks past the suffix back to the pool —
        the paged path's garbage lands in the row's own last partial page
        or the trash page.
        """
        x = self._embed(params, tokens).astype(compute_dtype)
        h, _, cache = self._backbone(params, x, ctx=ctx, cache=cache, pos=pos,
                                     paged_tables=block_tables, lens=lens)
        idx = jnp.maximum(lens - 1, 0)
        h_last = jnp.take_along_axis(
            h, idx[:, None, None].astype(jnp.int32), axis=1)
        return self._logits(params, h_last)[:, 0], cache

    def verify_chunk(self, params, tokens, cache, pos, lens, *,
                     ctx: ParallelCtx = CPU_CTX, compute_dtype=jnp.bfloat16,
                     block_tables=None):
        """Speculative-decoding verifier: ``prefill_chunk`` returning the
        logits at *every* position, (B, L, vocab), not just the last valid
        one.

        tokens: (B, L) int32 — row i is ``[last_committed, d_1..d_{L-1}]``,
        the request's last emitted token followed by its draft proposals;
        pos: (B,) start offsets (the request's ``cache_len``); lens: (B,)
        valid counts. Rides the identical row-offset attention path as
        ``prefill_chunk`` (the PR-4 L-token paged write path), so one call
        scores all L positions against the cache: ``logits[:, i]`` is the
        target's next-token distribution after consuming position
        ``pos + i``, which accept/reject compares with proposal ``d_{i+1}``.
        K/V for rejected tail tokens lands past the accepted length and is
        overwritten by the next round before any causal mask can expose it.
        """
        x = self._embed(params, tokens).astype(compute_dtype)
        h, _, cache = self._backbone(params, x, ctx=ctx, cache=cache, pos=pos,
                                     paged_tables=block_tables, lens=lens)
        return self._logits(params, h), cache

    def decode_step(self, params, tokens, cache, pos, *,
                    ctx: ParallelCtx = CPU_CTX, compute_dtype=jnp.bfloat16,
                    block_tables=None):
        """tokens: (B, 1) int32; pos: scalar int32 or (B,) int32 vector of
        per-request positions being written (continuous batching).

        With ``block_tables`` (B, nb) the cache is the paged view from
        ``BlockPool.paged_cache`` — attention layers read/write the page
        stores through the table indirection instead of a contiguous cache.
        """
        x = self._embed(params, tokens).astype(compute_dtype)
        h, _, cache = self._backbone(params, x, ctx=ctx, cache=cache, pos=pos,
                                     paged_tables=block_tables)
        return self._logits(params, h)[:, 0], cache


def build_lm(cfg: ModelConfig) -> LM:
    return LM(cfg)
