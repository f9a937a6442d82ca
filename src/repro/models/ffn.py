"""FFN family: GLU MLPs and Mixture-of-Experts.

MoE (DeepSeek-V2 form), for x one token's row:

    s = softmax(x W_router)          over all experts, in float32
    (w, i) = greedy top-k of s       w renormalized to sum 1 only where
                                     ``norm_topk_prob``
    y = sum_{e in i, e held} w_e SwiGLU_e(x)  +  SwiGLU_shared(x)

A layer holds the experts ``[e_start, e_start + e_count)`` of its config
(one expert-parallel share; all of them by default), routes over every
expert, and computes its own experts' part. Dispatch is dropless: every
row reaches every held expert it was routed to, whatever the batch shape,
so a row's output never depends on the other rows in the batch.

  * local   — one device holds the share: the N·k (row, expert)
              assignments sorted by expert, the held experts' groups
              first, run by grouped matmuls (``ragged_dot``) over the
              routed counts, so each held expert costs its own rows
  * sharded — expert-parallel ``shard_map`` over the ``model`` mesh axis:
              tokens stay put (replicated within a model row, as in Megatron
              TP), each device runs the local path on its slice of the held
              experts, partial outputs combine with the same ``psum`` dense
              TP already pays. No all-to-all, no token shuffling across the
              data axis.
  * capture — eager calibration: one read of the held experts' routed
              counts sizes each held expert's stack of routed rows, in
              token order, ``EXPERT_ROWS`` slots a chunk; one vmapped
              SwiGLU runs every held expert on a chunk, and every chunk's
              input and hidden stacks go to ``Calibrator.record_experts``
              for one grouped fold.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.common import ParallelCtx, act_fn, dense_init, split_key
from repro.models.linear import CaptureDict, linear_apply
from repro.obs import trace

# slots of each held expert's chunk in a capture's grouped fold
EXPERT_ROWS = 2048

# ---------------------------------------------------------------------------
# Dense FFN
# ---------------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, *, glu: bool = True, dtype=jnp.float32):
    ks = split_key(key, 3)
    p = {"up": {"w": dense_init(ks[0], d_model, d_ff, dtype)},
         "down": {"w": dense_init(ks[1], d_ff, d_model, dtype)}}
    if glu:
        p["gate"] = {"w": dense_init(ks[2], d_model, d_ff, dtype)}
    return p


def mlp_apply(params, x, act: str = "silu"):
    f = act_fn(act)
    if "gate" in params:
        h = f(linear_apply(params["gate"], x)) * linear_apply(params["up"], x)
    else:
        h = f(linear_apply(params["up"], x))
    return linear_apply(params["down"], h)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def moe_init(key, cfg, dtype=jnp.float32):
    d = cfg.d_model
    m = cfg.moe
    ks = split_key(key, 5)
    e, f = m.held, m.d_ff_expert

    def expert_stack(k, din, dout):
        return (jax.random.normal(k, (e, din, dout), jnp.float32)
                * (1.0 / math.sqrt(din))).astype(dtype)

    p = {
        "router": dense_init(ks[0], d, m.num_experts, jnp.float32),  # fp32
        "w_gate": expert_stack(ks[1], d, f),
        "w_up": expert_stack(ks[2], d, f),
        "w_down": expert_stack(ks[3], f, d),
    }
    if m.num_shared > 0:
        p["shared"] = mlp_init(ks[4], d, m.num_shared * f, glu=True, dtype=dtype)
    return p


def route(x_flat, router_w, moe):
    """Softmax scores over every expert in float32, and each row's greedy
    top-k: ``(scores (N, E), weights (N, k), experts (N, k))``."""
    logits = x_flat.astype(jnp.float32) @ router_w.astype(jnp.float32)
    scores = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(scores, moe.top_k)
    if moe.norm_topk_prob:
        top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-20)
    return scores, top_w, top_i


def _aux_loss(scores, top_i, moe):
    """Switch-style load balance: E * sum_e (share of rows routed to e) *
    (mean score of e)."""
    n = scores.shape[0]
    frac = jnp.zeros((moe.num_experts,), jnp.float32).at[
        top_i.reshape(-1)].add(1.0) / n
    return moe.num_experts * jnp.sum(frac * jnp.mean(scores, axis=0))


def _dispatch(top_w, top_i, e_start, e_count: int, rows: int):
    """Each held expert's routed rows in token order, padded to ``rows``
    slots (``rows`` >= every count, so nothing drops): row indices ``idx``
    (e_count, rows) with N in an empty slot, their weights ``wts``, and the
    routed ``counts`` (e_count,)."""
    n = top_i.shape[0]
    loc = top_i - e_start
    e_of = jnp.where((loc >= 0) & (loc < e_count), loc, e_count)  # drop
    tok = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None],
                           top_i.shape)
    member = jnp.zeros((e_count, n), bool).at[e_of, tok].set(
        True, mode="drop")
    wmat = jnp.zeros((e_count, n), top_w.dtype).at[e_of, tok].set(
        top_w, mode="drop")
    slot = jnp.where(member, jnp.cumsum(member, axis=1) - 1, rows)
    e_ix = jnp.arange(e_count)[:, None]
    idx = jnp.full((e_count, rows), n, jnp.int32).at[e_ix, slot].set(
        jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (e_count, n)),
        mode="drop")
    wts = jnp.zeros((e_count, rows), wmat.dtype).at[e_ix, slot].set(
        wmat, mode="drop")
    return idx, wts, member.sum(axis=1)


def _mm(x, w):
    if isinstance(w, tuple):              # COALA-factored expert: (b_t, a_t)
        return (x @ w[0]) @ w[1]
    return x @ w


def _gmm(x, w, sizes):
    """Grouped matmul: rows ``[sum(sizes[:e]), sum(sizes[:e+1]))`` of ``x``
    through expert ``e``'s weight; rows past every group give zeros."""
    if isinstance(w, tuple):              # COALA-factored expert: (b_t, a_t)
        return jax.lax.ragged_dot(jax.lax.ragged_dot(x, w[0], sizes),
                                  w[1], sizes)
    return jax.lax.ragged_dot(x, w, sizes)


def _experts(x_flat, idx, wts, wg, wu, wd, act: str):
    """Every held expert on its gathered rows at once: returns the weighted
    outputs scattered back to their rows (N, d), and the input (E, C, d)
    and hidden (E, C, f) stacks (an empty slot's rows are zero)."""
    n, d = x_flat.shape
    f = act_fn(act)
    wg, wu, wd = jax.tree.map(lambda a: a.astype(x_flat.dtype), (wg, wu, wd))
    x_pad = jnp.concatenate([x_flat, jnp.zeros((1, d), x_flat.dtype)])
    x_e = x_pad[idx]

    def one(x, g, u, dn):
        h = f(_mm(x, g)) * _mm(x, u)
        return h, _mm(h, dn)

    h_e, y_e = jax.vmap(one)(x_e, wg, wu, wd)
    y_e = y_e * wts[..., None].astype(y_e.dtype)
    out = jnp.zeros((n + 1, d), y_e.dtype).at[idx.reshape(-1)].add(
        y_e.reshape(-1, d))
    return out[:n], x_e, h_e


def _moe_local(x_flat, params, moe, e_start, e_count: int, act: str):
    """Route over every expert; compute the held experts' part, dropless,
    over N·k assignment rows (the most a held share can be routed)."""
    scores, top_w, top_i = route(x_flat, params["router"], moe)
    n, k = top_i.shape
    loc = top_i.reshape(-1) - e_start
    e_of = jnp.where((loc >= 0) & (loc < e_count), loc, e_count)
    order = jnp.argsort(e_of, stable=True)
    sizes = jnp.zeros((e_count + 1,), jnp.int32).at[e_of].add(1)[:e_count]
    tok = order // k
    held = e_of[order] < e_count
    f = act_fn(act)
    wg, wu, wd = jax.tree.map(lambda a: a.astype(x_flat.dtype),
                              (params["w_gate"], params["w_up"],
                               params["w_down"]))
    xs = x_flat[tok]
    h = f(_gmm(xs, wg, sizes)) * _gmm(xs, wu, sizes)
    y = _gmm(h, wd, sizes)
    w = top_w.reshape(-1)[order].astype(y.dtype)
    y = jnp.where(held[:, None], y * w[:, None], 0)
    out = jnp.zeros((n, x_flat.shape[1]), y.dtype).at[tok].add(y)
    return out, _aux_loss(scores, top_i, moe)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _route_held(x_flat, router_w, moe, rows: int):
    """Each row's top-k experts (N, k), the held experts' dispatch
    (``_dispatch``, ``rows`` slots each) and the aux loss."""
    scores, top_w, top_i = route(x_flat, router_w, moe)
    idx, wts, counts = _dispatch(top_w, top_i, moe.e_start, moe.held, rows)
    return top_i, idx, wts, counts, _aux_loss(scores, top_i, moe)


@functools.partial(jax.jit, static_argnames=("rows", "act"))
def _expert_chunk(acc, x_flat, idx, wts, j, wg, wu, wd, *, rows: int,
                  act: str):
    """Chunk ``j`` of ``rows`` slots of every held expert: one program for
    every chunk of every layer of one width."""
    take = lambda a: jax.lax.dynamic_slice_in_dim(a, j * rows, rows, axis=1)
    out, x_e, h_e = _experts(x_flat, take(idx), take(wts), wg, wu, wd, act)
    return acc + out, x_e, h_e


def _moe_capture(x_flat, params, moe, act: str, calib, path: str):
    """Eager calibration forward of the held experts. The routed counts are
    the layer's one read to the host; they size the chunks, and each
    held expert's stream folds only its true rows."""
    n = x_flat.shape[0]
    rows = min(EXPERT_ROWS, n)
    with trace.span("calib.route", path=path, experts=moe.held, tokens=n):
        _, idx, wts, counts, aux = _route_held(
            x_flat, params["router"], moe, -(-n // rows) * rows)
        counts = np.asarray(counts)
    out = jnp.zeros_like(x_flat)
    stacks = []
    for j in range(-(-int(counts.max(initial=0)) // rows)):
        out, x_e, h_e = _expert_chunk(
            out, x_flat, idx, wts, j, params["w_gate"], params["w_up"],
            params["w_down"], rows=rows, act=act)
        stacks.append((x_e, h_e))
    calib.record_experts(path, moe.e_start, stacks, counts)
    return out, aux


def moe_apply(cfg, params, x, *, ctx: ParallelCtx) -> Tuple[jax.Array, jax.Array]:
    """x: (B, T, d) -> (y, aux_loss)."""
    b, t, d = x.shape
    m = cfg.moe
    xf = x.reshape(b * t, d)

    if isinstance(params, CaptureDict) and params.calib is not None:
        y, aux = _moe_capture(xf, params, m, cfg.act, params.calib,
                              params.path)
        y = y.reshape(b, t, d)
    elif ctx.mesh is not None and ctx.shard_map_moe:
        e_loc = m.held // ctx.model_size
        assert e_loc * ctx.model_size == m.held, \
            f"held experts {m.held} must divide model axis {ctx.model_size}"
        n_shards = 1
        for a in ctx.batch_axes:
            n_shards *= ctx.mesh.shape[a]
        if b % n_shards:          # tiny-batch decode: replicate tokens instead
            bspec = P(None, None, None)
        else:
            bspec = P(ctx.batch_axes, None, None)
        espec = P(ctx.model_axis, None, None)

        def body(x_loc, router_w, wg, wu, wd):
            bl, tl, _ = x_loc.shape
            me = jax.lax.axis_index(ctx.model_axis)
            p_loc = {"router": router_w, "w_gate": wg, "w_up": wu, "w_down": wd}
            out, aux = _moe_local(x_loc.reshape(bl * tl, d), p_loc, m,
                                  m.e_start + me * e_loc, e_loc, cfg.act)
            out = jax.lax.psum(out, ctx.model_axis)
            aux = jax.lax.pmean(aux, ctx.model_axis)
            return out.reshape(bl, tl, d), aux

        def etree(w):                      # dense array or factored tuple
            return jax.tree.map(lambda _: espec, w)

        y, aux = jax.shard_map(
            body, mesh=ctx.mesh,
            in_specs=(bspec, P(), etree(params["w_gate"]),
                      etree(params["w_up"]), etree(params["w_down"])),
            out_specs=(bspec, P()), check_vma=False,
        )(x, params["router"], params["w_gate"], params["w_up"], params["w_down"])
    else:
        y, aux = _moe_local(xf, params, m, m.e_start, m.held, cfg.act)
        y = y.reshape(b, t, d)

    if "shared" in params:
        y = y + mlp_apply(params["shared"], x, cfg.act)
    return y, aux * cfg.moe.aux_loss_weight
