"""Shared model building blocks: norms, RoPE/M-RoPE, softcap, parallel context."""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    """Runtime parallelism context threaded through model apply fns.

    ``mesh is None`` -> single-device math everywhere (CPU tests).
    """
    mesh: Optional[object] = None                   # jax.sharding.Mesh
    batch_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"
    shard_map_moe: bool = False                     # expert-parallel MoE path
    dense_attn_max_seq: int = 2048                  # above this -> chunked attn
    attn_chunk_q: int = 2048
    attn_chunk_kv: int = 1024
    causal_pair_scan: bool = False                  # §Perf: skip masked kv blocks
    use_pallas: bool = False                        # TPU flash-attention kernel
    mlstm_chunkwise: bool = False                   # chunkwise-parallel mLSTM
    paged_attn_impl: Optional[str] = None           # paged decode kernel: None/
                                                    # "auto" | "pallas" | "ref"

    @property
    def model_size(self) -> int:
        if self.mesh is None:
            return 1
        return self.mesh.shape[self.model_axis]


CPU_CTX = ParallelCtx()


def constrain_act(x, ctx: "ParallelCtx"):
    """Pin activations to (batch-sharded, replicated-features) at block
    boundaries. Without this GSPMD drifts activations through partial
    feature shardings and pays reshard collectives every layer (measured:
    ~40% of gemma2 train link bytes — see EXPERIMENTS.md §Perf)."""
    if ctx.mesh is None:
        return x
    from jax.sharding import AxisType, NamedSharding, PartitionSpec
    # inside a partially-manual shard_map (e.g. the pod-manual gradient
    # compression region) the manual axes may not appear in constraints
    am = jax.sharding.get_abstract_mesh()
    manual = {n for n, t in zip(am.axis_names, am.axis_types)
              if t == AxisType.Manual}
    axes = tuple(a for a in ctx.batch_axes if a not in manual)
    if not axes:
        return x
    b = x.shape[0] if hasattr(x, "shape") and x.ndim else 0
    n_shards = 1
    for a in axes:
        n_shards *= ctx.mesh.shape[a]
    if not b or b % n_shards:
        return x
    spec = PartitionSpec(axes, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.zeros((d,), dtype)}        # stored as (1+scale) gemma-style


def rmsnorm(params, x, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + params["scale"].astype(jnp.float32))).astype(dt)


def nonparametric_ln(x, eps: float = 1e-5):
    """OLMo-style LayerNorm without learnable scale/bias."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(dt)


def make_norm(cfg):
    """Returns (init_fn() -> params, apply_fn(params, x))."""
    if cfg.nonparametric_norm:
        return (lambda: {}), (lambda p, x: nonparametric_ln(x, cfg.norm_eps))
    return (lambda: rmsnorm_init(cfg.d_model)), \
           (lambda p, x: rmsnorm(p, x, cfg.norm_eps))


def softcap(x, cap: float):
    if cap <= 0:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# RoPE (standard + M-RoPE)
# ---------------------------------------------------------------------------

def _host_freqs(head_dim: int, theta: float) -> np.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (np.arange(half) / half))


def rope_freqs(head_dim: int, theta: float, dtype=jnp.float32):
    """Inverse frequencies theta^(-2i/d), computed on the host in float64
    and rounded once: a device's float32 ``pow`` is off by ~1e-6 of the
    value, which at position 2047 turns the angle by ~1e-3 rad."""
    return jnp.asarray(_host_freqs(head_dim, theta), dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(head_dim: int, theta: float, yarn):
    """YaRN inverse frequencies (HF ``DeepseekV2YarnRotaryEmbedding``): the
    plain ones at the fast dims, divided by ``factor`` at the slow dims,
    blended by a linear ramp between the correction dims; on the host in
    float64, as ``rope_freqs``."""
    half = head_dim // 2

    def corr_dim(rot):
        return (head_dim * math.log(yarn.original_max_position_embeddings
                                    / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    extra = _host_freqs(head_dim, theta)
    return jnp.asarray(extra / yarn.factor * ramp + extra * (1.0 - ramp),
                       jnp.float32)


def rope_cos_sin(positions, head_dim: int, theta: float, yarn=None):
    """positions: (..., T) int -> cos/sin (..., T, head_dim//2). With
    ``yarn`` the frequencies are YaRN's and cos/sin carry its
    mscale / mscale_all_dim factor."""
    if yarn is None:
        ang = positions[..., None].astype(jnp.float32) * \
            rope_freqs(head_dim, theta)
        return jnp.cos(ang), jnp.sin(ang)
    ang = positions[..., None].astype(jnp.float32) * \
        yarn_freqs(head_dim, theta, yarn)
    scale = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def apply_rope(x, cos, sin):
    """x: (B, T, H, hd); cos/sin: (B, T, hd//2) or (T, hd//2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:                                # (T, half)
        cos_ = cos[None, :, None, :]
        sin_ = sin[None, :, None, :]
    else:                                            # (B, T, half)
        cos_ = cos[:, :, None, :]
        sin_ = sin[:, :, None, :]
    cos_, sin_ = cos_.astype(x.dtype), sin_.astype(x.dtype)
    return jnp.concatenate([x1 * cos_ - x2 * sin_,
                            x2 * cos_ + x1 * sin_], axis=-1)


def mrope_cos_sin(position_ids, head_dim: int, theta: float,
                  sections: Tuple[int, int, int]):
    """Qwen2-VL M-RoPE. position_ids: (3, B, T) for (t, h, w) streams.

    ``sections`` split head_dim//2 frequency slots among the three streams.
    Returns cos/sin of shape (B, T, head_dim//2).
    """
    half = head_dim // 2
    assert sum(sections) == half, (sections, half)
    inv = rope_freqs(head_dim, theta)                # (half,)
    ang = position_ids[..., None].astype(jnp.float32) * inv  # (3, B, T, half)
    splits = []
    start = 0
    for i, sec in enumerate(sections):
        splits.append(ang[i, :, :, start:start + sec])
        start += sec
    ang_sel = jnp.concatenate(splits, axis=-1)       # (B, T, half)
    return jnp.cos(ang_sel), jnp.sin(ang_sel)


# ---------------------------------------------------------------------------
# Misc
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32, scale: float = 1.0):
    std = scale / jnp.sqrt(jnp.asarray(d_in, jnp.float32))
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * std).astype(dtype)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
            "relu": jax.nn.relu}[name]


def split_key(key, n: int):
    return list(jax.random.split(key, n))


def chunked_scan(f, init, xs, chunk: int, *, time_axis: int = 0):
    """``lax.scan`` over time with chunk-boundary checkpointing.

    A naive scan saves every per-step carry for backward — O(T·state) memory,
    prohibitive for recurrent layers (mamba/mLSTM/sLSTM) at 4k tokens. This
    wrapper scans over T/chunk chunks, saving ONLY chunk-boundary carries and
    rematerializing the inner steps in backward: memory O(T/chunk · state),
    compute overhead ≤ 2x on the recurrence (not on the projections).

    xs: pytree with leading time axis T (divisible chunking handled by
    falling back to plain scan when T % chunk != 0).
    """
    leaves = jax.tree.leaves(xs)
    t = leaves[0].shape[time_axis]
    if chunk <= 0 or t % chunk or t <= chunk:
        return jax.lax.scan(f, init, xs)
    n = t // chunk

    def reshape(x):
        return x.reshape((n, chunk) + x.shape[1:])

    xs_c = jax.tree.map(reshape, xs)

    @jax.checkpoint
    def chunk_body(carry, xs_chunk):
        return jax.lax.scan(f, carry, xs_chunk)

    carry, ys = jax.lax.scan(chunk_body, init, xs_c)

    def unshape(y):
        return y.reshape((t,) + y.shape[2:])

    return carry, jax.tree.map(unshape, ys)
