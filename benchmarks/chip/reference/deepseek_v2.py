"""Plain float32 reference of DeepSeek-V2(-Lite): MLA attention, a dense
SwiGLU first layer, then softmax top-k mixture-of-experts layers with
shared experts, at one chip's expert share.

No cache, no kernel, no capacity: the published forward pass (HF
``modeling_deepseek.py``) in ``jax.numpy`` under ``"highest"`` matmul
precision, one layer at a time. It imports nothing of the program under
test. It reads the weights in the program's tree layout, which the
benchmark draws itself:

- ``embed`` (vocab, d); ``lm_head/w`` (d, vocab), untied; ``final_norm``;
- layer 0 ``prefix/0``, layers 1.. ``blocks/sub0`` stacked on axis 0, each
  ``{norm1, mixer/{wq, wkv_a, kv_norm, wkv_b, wo}, norm2, ffn}``;
- a dense ``ffn`` is ``{gate, up, down}``; a MoE ``ffn`` is ``{router (d,
  E), w_gate / w_up (held, d, f), w_down (held, f, d), shared/{gate, up,
  down}}``, the held experts being ``e_start .. e_start + held - 1``.

The layer, for x (T, d) one sequence:

    a = RMSNorm(x)                  x rsqrt(mean x^2 + eps) (1 + scale)
    [q_nope, q_pe] = a W_q          per head dn + dr
    [c, k_pe]      = a W_kv_a       c RMSNorm'd, k_pe shared by the heads
    [k_nope, v]    = c W_kv_b       per head dn + dv
    q_pe, k_pe: pairs (2i, 2i+1) read as halves (HF's de-interleave), then
        rotated by YaRN frequencies (cos and sin times mscale/mscale_all_dim)
    o = softmax_causal((q_nope.k_nope + q_pe.k_pe) / sqrt(dn+dr) * m^2) v
        with m = 0.1 mscale_all_dim ln(factor) + 1
    x += o W_o
    m = RMSNorm(x)
    dense: x += SwiGLU(m); MoE: s = softmax(m W_router) in float32 over
        every expert, greedy top-k, weights s * routed_scaling_factor
        (renormalized first only where ``norm_topk_prob``),
        x += sum over the held experts among the top-k of w_e SwiGLU_e(m)
           + SwiGLU_shared(m)

The absent experts' part is left out, as the program leaves it out. Each
held expert runs on every row, its output weighted by zero where the row
was not routed to it: plain, and dropless by construction.

Routing near-ties (``route``): where the program's top-k for a row (passed
as ``forced``) is not the reference's, the reference measures how far the
program's least expert lies below its own k-th, in router logits; a row
within ``delta`` of that takes the program's choice (a near-tie: the two
orders differ by rounding); each row's distance is returned, for the
check to count the rows beyond ``delta`` as routing faults.

``quant="bf16x3"`` is the control: every matmul at three bfloat16 passes
(float32 at ``"high"``), spelled out in ``transformer._ein``.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.reference.transformer import _ein

# the taps of a layer: ``attn_in`` (W_q, W_kv_a), ``latent`` (W_kv_b),
# ``attn_out`` (W_o), ``mlp_in``, ``mlp_hidden`` (dense or shared expert),
# and ``expert<e>/in``, ``expert<e>/hid`` for each held expert


def norm(p, x, eps: float):
    """HF ``DeepseekV2RMSNorm``: x * rsqrt(mean(x^2) + eps) * weight."""
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * \
        (1.0 + p["scale"].astype(jnp.float32))


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_tables(arch: Dict[str, Any], t: int):
    """cos, sin (T, dr/2) of HF ``DeepseekV2YarnRotaryEmbedding``, float32
    as HF computes them: the inverse frequencies rounded to float32, the
    angle position x frequency a float32 product, its cos and sin (in
    float64, then rounded)."""
    dim, base = arch["qk_rope_head_dim"], arch["rope_theta"]
    y = arch["rope_scaling"]
    half = dim // 2
    extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    scale = 1.0
    if y is not None:
        orig = y["original_max_position_embeddings"]

        def corr(rot):
            return (dim * math.log(orig / (rot * 2 * math.pi))
                    / (2 * math.log(base)))

        low = max(math.floor(corr(y["beta_fast"])), 0)
        high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(half) - low) / (high - low), 0, 1)
        inv = extra / y["factor"] * ramp + extra * (1 - ramp)
        scale = (_yarn_mscale(y["factor"], y["mscale"])
                 / _yarn_mscale(y["factor"], y["mscale_all_dim"]))
    else:
        inv = extra
    ang = np.outer(np.arange(t, dtype=np.float32), inv.astype(np.float32))
    ang = ang.astype(np.float64)
    return (jnp.asarray(np.cos(ang) * scale, jnp.float32),
            jnp.asarray(np.sin(ang) * scale, jnp.float32))


def rope(x, cos, sin):
    """x (T, H, dr): de-interleave, then ``rotate_half``."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    half = x.shape[-1] // 2
    c, s = cos[:, None, :], sin[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def softmax_scale(arch: Dict[str, Any]) -> float:
    scale = (arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"]) ** -0.5
    y = arch["rope_scaling"]
    if y is not None and y.get("mscale_all_dim"):
        scale *= _yarn_mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return scale


def _swiglu(m, g, u, d, quant):
    h = jax.nn.silu(_ein("ti,if->tf", m, g, quant)) * \
        _ein("ti,if->tf", m, u, quant)
    return h, _ein("tf,fi->ti", h, d, quant)


def route(arch, logits, forced=None, delta: float = 0.0):
    """Routing of T rows from router logits (T, E), float32: the (T, E)
    weight matrix (zero off the top-k), the rows that took the program's
    choice, and per row the distance by which the forced choice's least
    expert lies below the reference's own k-th logit (0 where the two
    agree; a row beyond ``delta`` keeps the reference's choice)."""
    k = arch["num_experts_per_tok"]
    scores = jax.nn.softmax(logits, axis=-1)
    mine = jax.lax.top_k(scores, k)[1]          # HF: topk of the scores
    if forced is None:
        dist = jnp.zeros(logits.shape[0], jnp.float32)
        chosen, flipped = mine, dist > 0
    else:
        forced = jnp.asarray(forced, jnp.int32)
        same = jnp.all(jnp.sort(mine, -1) == jnp.sort(forced, -1), axis=-1)
        kth = jnp.min(jnp.take_along_axis(logits, mine, -1), axis=-1)
        low = jnp.min(jnp.take_along_axis(logits, forced, -1), axis=-1)
        dist = jnp.where(same, 0.0, kth - low)
        flipped = ~same & (dist <= delta)
        chosen = jnp.where(flipped[:, None], forced, mine)
    w = jnp.take_along_axis(scores, chosen, -1)
    if arch["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * arch["routed_scaling_factor"]
    gw = jnp.zeros_like(scores).at[
        jnp.arange(scores.shape[0])[:, None], chosen].set(w)
    return gw, flipped, dist


def layer(arch, lp, x, *, moe: bool, quant=None, taps=None, forced=None,
          delta: float = 0.0):
    """One decoder layer on one sequence x (T, d), float32. Returns
    ``(x, flipped rows, forced distances)`` (``route``); ``taps``, where given,
    receives each linear's input and each held expert's routed rows (as a
    0/1 row mask ``expert<e>/mask`` beside the full-row ``expert<e>/in``
    and ``/hid``)."""
    t = x.shape[0]
    h, dn, dr, dv = (arch["num_attention_heads"], arch["qk_nope_head_dim"],
                     arch["qk_rope_head_dim"], arch["v_head_dim"])
    kl, eps = arch["kv_lora_rank"], arch["rms_norm_eps"]
    f32 = lambda a: a.astype(jnp.float32)
    mix, ffn = lp["mixer"], lp["ffn"]
    a = norm(lp["norm1"], x, eps)
    q = _ein("td,de->te", a, f32(mix["wq"]["w"]), quant).reshape(t, h, dn + dr)
    kv = _ein("td,de->te", a, f32(mix["wkv_a"]["w"]), quant)
    c = norm(mix["kv_norm"], kv[:, :kl], eps)
    kvb = _ein("tk,ke->te", c, f32(mix["wkv_b"]["w"]), quant).reshape(
        t, h, dn + dv)
    cos, sin = rope_tables(arch, t)
    q_pe = rope(q[..., dn:], cos, sin)
    k_pe = rope(kv[:, None, kl:], cos, sin)[:, 0]
    s = (_ein("qhd,shd->hqs", q[..., :dn], kvb[..., :dn], quant)
         + _ein("qhd,sd->hqs", q_pe, k_pe, quant)) * softmax_scale(arch)
    causal = np.tril(np.ones((t, t), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _ein("hqs,shd->qhd", p, kvb[..., dn:], quant).reshape(t, h * dv)
    x = x + _ein("te,ed->td", o, f32(mix["wo"]["w"]), quant)
    m = norm(lp["norm2"], x, eps)
    found = {"attn_in": a, "latent": c, "attn_out": o, "mlp_in": m}
    dist = jnp.zeros(t, jnp.float32)
    flipped = dist > 0
    if moe:
        y, flipped, dist = moe_ffn(arch, ffn, m, quant, forced, delta, found)
    else:
        u, y = _swiglu(m, f32(ffn["gate"]["w"]), f32(ffn["up"]["w"]),
                       f32(ffn["down"]["w"]), quant)
        found["mlp_hidden"] = u
    if taps is not None:
        taps.update(found)
    return x + y, flipped, dist


def moe_ffn(arch, ffn, m, quant=None, forced=None, delta: float = 0.0,
            found=None):
    """The MoE FFN on m (T, d): the shared expert plus the held experts'
    part. Returns ``(y, flipped rows, forced distances)``; ``found``
    receives the taps."""
    f32 = lambda a: a.astype(jnp.float32)
    found = {} if found is None else found
    sh = ffn["shared"]
    u, y = _swiglu(m, f32(sh["gate"]["w"]), f32(sh["up"]["w"]),
                   f32(sh["down"]["w"]), quant)
    found["mlp_hidden"] = u
    logits = _ein("td,de->te", m, f32(ffn["router"]), quant)
    gw, flipped, dist = route(arch, logits, forced, delta)
    e0 = arch["e_start"]
    for e in range(ffn["w_gate"].shape[0]):
        ue, ye = _swiglu(m, f32(ffn["w_gate"][e]), f32(ffn["w_up"][e]),
                         f32(ffn["w_down"][e]), quant)
        w = gw[:, e0 + e]
        y = y + ye * w[:, None]
        found[f"expert{e0 + e}/in"] = m
        found[f"expert{e0 + e}/hid"] = ue
        found[f"expert{e0 + e}/mask"] = (w > 0).astype(jnp.float32)
    return y, flipped, dist


def layer_params(params, i: int):
    """Layer ``i``: the dense first layer, then the stacked MoE layers."""
    if i == 0:
        return params["prefix"][0]
    return jax.tree.map(lambda a: a[i - 1], params["blocks"]["sub0"])


def items(arch: Dict[str, Any]):
    """The architecture as a hashable static argument."""
    return tuple(sorted((k, tuple(sorted(v.items())) if isinstance(v, dict)
                         else v) for k, v in arch.items()))


def _arch(items):
    return {k: dict(v) if isinstance(v, tuple) else v for k, v in items}


@jax.jit
def embed(table, tokens):
    return table.astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def layer_grams(items, moe: bool, quant, lp, x, forced, delta):
    """One layer on one sequence: ``(x, grams, flipped, distances)``, the
    Gram of every tap (an expert's over its routed rows alone)."""
    with jax.default_matmul_precision("highest"):
        taps: Dict[str, jax.Array] = {}
        x, flipped, dist = layer(_arch(items), lp, x, moe=moe, quant=quant,
                                 taps=taps, forced=forced, delta=delta)
        grams = {}
        for k, a in taps.items():
            if k.endswith("/mask"):
                continue
            mask = taps.get(k.rsplit("/", 1)[0] + "/mask")
            am = a if mask is None else a * mask[:, None]
            grams[k] = _ein("ti,tj->ij", am, am, quant)
        return x, grams, flipped, dist


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_only(items, moe: bool, lp, x):
    with jax.default_matmul_precision("highest"):
        return layer(_arch(items), lp, x, moe=moe)[0]


@functools.partial(jax.jit, static_argnums=(0,))
def _head(items, final_norm, head_w, x):
    with jax.default_matmul_precision("highest"):
        h = norm(final_norm, x, _arch(items)["rms_norm_eps"])
        return h @ head_w.astype(jnp.float32)


def logits(arch: Dict[str, Any], params, tokens) -> jax.Array:
    """Logits (B, T, vocab) in float32 for tokens (B, T), a sequence at a
    time."""
    static = items(arch)
    out = []
    for row in np.asarray(tokens):
        x = embed(params["embed"], jnp.asarray(row, jnp.int32))
        for i in range(arch["num_hidden_layers"]):
            x = _layer_only(static, is_moe(arch, i),
                            layer_params(params, i), x)
        out.append(_head(static, params["final_norm"],
                         params["lm_head"]["w"], x))
    return jnp.stack(out)


def is_moe(arch: Dict[str, Any], i: int) -> bool:
    return i >= arch["first_k_dense_replace"]
