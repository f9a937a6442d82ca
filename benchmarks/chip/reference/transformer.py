"""Plain float32 reference of a dense decoder LM (SmolLM).

No cache, no kernel, no batching: the published forward pass in
``jax.numpy`` under ``"highest"`` matmul precision, one layer at a time so
that a model at its published widths fits beside nothing else. It imports
nothing of the program under test. It reads the weights in the program's
tree layout (``embed``, ``final_norm``, ``blocks/sub0/{norm1, mixer/{wq,
wk, wv, wo}, norm2, ffn/{gate, up, down}}``, each layer stacked on axis 0),
which the benchmark draws itself.

The architecture comes from the configuration file (``configs/*.json``):

- ``norm``: ``"rmsnorm"`` (x / rms(x) * (1 + scale); the layout keeps an
  RMSNorm weight as ``1 + scale``); ``norm_eps``;
- rotary embedding on the two halves of each head (HF ``rotate_half``),
  ``rope_theta``; query head ``h`` reads key/value head ``h // (heads /
  kv_heads)``; scores scaled by 1/sqrt(head_dim); causal softmax;
- SwiGLU MLP, silu(x W_gate) * (x W_up) W_down; LM head tied to ``embed``.

``quant="fp8"`` rounds every matmul operand to float8 (e4m3) with one
scale per row of the contraction, the control that ``correct`` must
reject: the lower precision below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0


def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _split(x):
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _ein(spec: str, a, b, quant: Optional[str]):
    """einsum in float32. ``quant="fp8"`` rounds the operands to float8
    first, each scaled along the axes it is contracted over;
    ``quant="bf16x3"`` is float32 at ``"high"`` spelled out, the same on
    every backend: each operand split into two bfloat16 parts and the
    three largest of the four products summed in float32."""
    if quant == "fp8":
        ins, out = spec.split("->")
        sa, sb = ins.split(",")
        contracted = set(sa) & set(sb) - set(out)
        a = _q8(a, tuple(i for i, c in enumerate(sa) if c in contracted))
        b = _q8(b, tuple(i for i, c in enumerate(sb) if c in contracted))
    elif quant == "bf16x3":
        (ah, al), (bh, bl) = _split(a), _split(b)
        e = lambda x, y: jnp.einsum(spec, x, y,
                                    preferred_element_type=jnp.float32)
        return e(ah, bh) + (e(ah, bl) + e(al, bh))
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return jnp.einsum(spec, a, b)


def norm(arch: Dict[str, Any], p, x):
    eps = arch["norm_eps"]
    if arch["norm"] == "rmsnorm":
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x / jnp.sqrt(ms + eps) * (1.0 + p["scale"].astype(jnp.float32))
    raise ValueError(f"unknown norm {arch['norm']!r}")


def rope(x, theta: float):
    """x: (B, T, H, hd), positions 0..T-1."""
    t, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(arch, lp, x, quant=None, taps=None):
    """One decoder layer on x (B, T, d), float32. ``taps``, where given,
    is a dict that receives the input of each linear."""
    b, t, _ = x.shape
    h, kv, hd = arch["heads"], arch["kv_heads"], arch["head_dim"]
    f32 = lambda a: a.astype(jnp.float32)
    mix, ffn = lp["mixer"], lp["ffn"]
    a = norm(arch, lp.get("norm1", {}), x)
    q = _ein("btd,de->bte", a, f32(mix["wq"]["w"]), quant)
    k = _ein("btd,de->bte", a, f32(mix["wk"]["w"]), quant)
    v = _ein("btd,de->bte", a, f32(mix["wv"]["w"]), quant)
    q = rope(q.reshape(b, t, h, hd), arch["rope_theta"])
    k = rope(k.reshape(b, t, kv, hd), arch["rope_theta"])
    v = v.reshape(b, t, kv, hd)
    g = h // kv
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    s = _ein("bqhd,bshd->bhqs", q, k, quant) / np.sqrt(hd)
    causal = np.tril(np.ones((t, t), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _ein("bhqs,bshd->bqhd", p, v, quant).reshape(b, t, h * hd)
    x = x + _ein("btd,de->bte", o, f32(mix["wo"]["w"]), quant)
    m = norm(arch, lp.get("norm2", {}), x)
    gate = _ein("btd,df->btf", m, f32(ffn["gate"]["w"]), quant)
    up = _ein("btd,df->btf", m, f32(ffn["up"]["w"]), quant)
    u = jax.nn.silu(gate) * up
    x = x + _ein("btf,fd->btd", u, f32(ffn["down"]["w"]), quant)
    if taps is not None:
        taps.update(attn_in=a, attn_out=o, mlp_in=m, mlp_hidden=u)
    return x


def _layer_params(params, i: int):
    return jax.tree.map(lambda a: a[i], params["blocks"]["sub0"])


@jax.jit
def _embed(embed, tokens):
    return embed.astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("quant",))
def _layer_jit(arch_items, lp, x, quant=None):
    with jax.default_matmul_precision("highest"):
        return layer(dict(arch_items), lp, x, quant)


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("quant",))
def _head(arch_items, final_norm, embed, x, quant=None):
    with jax.default_matmul_precision("highest"):
        h = norm(dict(arch_items), final_norm, x)
        return _ein("btd,vd->btv", h, embed.astype(jnp.float32), quant)


def logits(arch: Dict[str, Any], params, tokens, quant=None):
    """Logits (B, T, vocab) in float32 for tokens (B, T)."""
    items = tuple(sorted(arch.items()))
    x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
    for i in range(arch["layers"]):
        x = _layer_jit(items, _layer_params(params, i), x, quant=quant)
    return _head(items, params.get("final_norm", {}), params["embed"], x,
                 quant=quant)
