"""PartitionSpec factories for every architecture in ``repro.configs``.

One vocabulary, three mesh axes:

  * ``pod``   — slow cross-pod links (DCN). Parameters are **replicated**
                across pods (the int8+EF gradient compression in
                ``train/grad_compress.py`` owns the cross-pod reduction and
                expects pod-replicated params); batches shard over it.
  * ``data``  — fast intra-pod data parallelism. Batches always shard over
                it; in ``mode="train"`` parameters/optimizer state also
                FSDP-shard over it (ZeRO-3 style).
  * ``model`` — tensor parallelism: column-parallel in-projections,
                row-parallel out-projections, vocab-sharded embedding/head,
                expert-parallel MoE banks (the expert axis shards over
                ``model``, matching the ``shard_map`` MoE path in
                ``models/ffn.py``), and kv-head-sharded attention caches.

Every spec is divisibility-guarded: an axis is only assigned to a tensor
dimension the mesh divides evenly, so the same code serves the 8-fake-device
CPU test meshes and the 512-device production meshes in ``launch/dryrun.py``.
Stacked-layer parameters (under ``blocks`` / ``enc`` / ``dec``) carry their
leading scan axis unsharded.

The public API is exactly what ``launch/train.py``, ``launch/dryrun.py`` and
``tests/test_dist.py`` import: ``param_specs``, ``batch_specs``,
``cache_specs``, ``train_state_specs``, ``to_named``, ``batch_axes_of``.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

MODEL_AXIS = "model"
# batch-like axes in mesh-major order; only those present in a mesh apply
BATCH_AXES = ("pod", "data")
# FSDP shards parameters over the intra-pod data axis only — never over
# ``pod`` (grad compression needs pod-replicated params, and the error-state
# spec P("pod", *param_spec) must not mention pod twice)
FSDP_AXES = ("data",)

# role of each named linear, keyed by the last meaningful path component.
# col: (d_in, d_out) with d_out model-sharded (in-projections / up-projections)
# row: (d_in, d_out) with d_in model-sharded (out-projections / down-projections)
_COL_KEYS = frozenset({
    "wq", "wk", "wv",                 # GQA / MLA / cross-attention queries
    "gate", "up", "ff_up",            # GLU MLP + sLSTM feed-forward
    "in_proj",                        # mamba input projection
    "w_dkv", "w_krope",               # MLA latent down-projections
    "w_uk", "w_uv",                   # MLA latent up-projections (raw arrays)
    "x_proj", "dt_proj",              # mamba SSM parameter projections
})
_ROW_KEYS = frozenset({
    "wo", "down", "ff_down",          # attention / MLP output projections
    "out_proj",                       # mamba / xlstm output projection
})
# MoE expert banks: (E, d_in, d_out) stacks, expert axis over ``model``
_EXPERT_KEYS = frozenset({"w_gate", "w_up", "w_down"})
# stacked-layer containers whose leaves carry a leading scan axis
_STACKED_KEYS = frozenset({"blocks", "enc", "dec"})


def batch_axes_of(mesh) -> Tuple[str, ...]:
    """The mesh's batch-parallel axes (``pod``/``data``), mesh order."""
    return tuple(a for a in BATCH_AXES if a in mesh.axis_names)


def to_named(specs, mesh):
    """Map a PartitionSpec tree to a NamedSharding tree on ``mesh``."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _axis_size(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _fit(dim: int, mesh, axes):
    """``axes`` if they evenly divide ``dim`` (and exist on the mesh), else
    None. ``axes`` may be a name or a tuple of names."""
    if axes is None:
        return None
    if isinstance(axes, str):
        axes = (axes,)
    axes = tuple(a for a in axes if a in mesh.axis_names)
    if not axes:
        return None
    size = _axis_size(mesh, axes)
    if size <= 1 or dim % size:
        return None
    return axes[0] if len(axes) == 1 else axes


def _path_names(path) -> Tuple[str, ...]:
    """jax key path -> plain string components."""
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        else:
            out.append(str(p))
    return tuple(out)


def _role(names: Tuple[str, ...]) -> str:
    """Last meaningful path component (skips the 'w' / factor leaf names)."""
    skip = {"w", "b_t", "a_t"}
    for name in reversed(names):
        if name not in skip:
            return name
    return names[-1] if names else ""


def _with_lead(spec_entries, lead: int) -> P:
    return P(*([None] * lead), *spec_entries)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def param_specs(cfg, params, mesh, *, mode: str = "train"):
    """PartitionSpec tree for a parameter pytree.

    ``mode="train"``  — FSDP over ``data`` *plus* tensor parallelism over
                        ``model`` (ZeRO-3-style fully sharded master).
    ``mode="infer"``  — tensor parallelism only; params replicated over the
                        batch axes (decode never pays FSDP all-gathers).
    """
    if mode not in ("train", "infer"):
        raise ValueError(f"param_specs: unknown mode {mode!r}")
    fsdp = FSDP_AXES if mode == "train" else ()

    def leaf_spec(path, leaf) -> P:
        names = _path_names(path)
        shape = tuple(leaf.shape)
        lead = 1 if any(n in _STACKED_KEYS for n in names) else 0
        body = shape[lead:]
        role = _role(names)

        if role in _EXPERT_KEYS and len(body) == 3:
            # (E, d_in, d_out): expert-parallel over model (ffn.py shard_map)
            e, d_in, _ = body
            return _with_lead((_fit(e, mesh, MODEL_AXIS),
                               _fit(d_in, mesh, fsdp), None), lead)
        if role == "embed" and len(body) == 2:
            # (vocab, d_model): vocab-sharded TP; FSDP over features
            v, d = body
            return _with_lead((_fit(v, mesh, MODEL_AXIS),
                               _fit(d, mesh, fsdp)), lead)
        if role == "lm_head" and len(body) == 2:
            d, v = body
            return _with_lead((_fit(d, mesh, fsdp),
                               _fit(v, mesh, MODEL_AXIS)), lead)
        if role in _COL_KEYS and len(body) == 2:
            d_in, d_out = body
            # factored low-rank pairs: only the dense-facing dim is sharded
            model_dim = None if names[-1] == "b_t" else \
                _fit(d_out, mesh, MODEL_AXIS)
            return _with_lead((_fit(d_in, mesh, fsdp), model_dim), lead)
        if role in _ROW_KEYS and len(body) == 2:
            d_in, d_out = body
            model_dim = None if names[-1] == "a_t" else \
                _fit(d_in, mesh, MODEL_AXIS)
            return _with_lead((model_dim, _fit(d_out, mesh, fsdp)), lead)
        # everything else (norm scales, routers, gates, conv/recurrence
        # params, positional tables) is small: replicate
        return P()

    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def train_state_specs(cfg, state, mesh, *, strategy: str = "fsdp"):
    """Specs for the full train state ``{"params", "opt", ["err"]}``.

    ``fsdp``   — params and AdamW moments fully sharded (ZeRO-3).
    ``zero1``  — params TP-only (replicated over data), moments sharded
                 (ZeRO-1); the hoisted-cast variant (``zero1h``) uses the
                 same state specs plus an ``infer``-mode compute copy, wired
                 by the caller via ``make_train_step(compute_specs=...)``.
    """
    if strategy not in ("fsdp", "zero1", "zero1h"):
        raise ValueError(f"train_state_specs: unknown strategy {strategy!r}")
    opt_specs = param_specs(cfg, state["params"], mesh, mode="train")
    if strategy == "fsdp":
        p_specs = opt_specs
    else:
        p_specs = param_specs(cfg, state["params"], mesh, mode="infer")
    out = {"params": p_specs,
           "opt": {"m": opt_specs, "v": opt_specs, "step": P()}}
    if state.get("err") is not None:
        # error-feedback residuals: explicit leading pod axis over the
        # (pod-free) param specs — see train/grad_compress.py
        out["err"] = jax.tree.map(lambda s: P("pod", *tuple(s)), p_specs,
                                  is_leaf=lambda x: isinstance(x, P))
    return out


# ---------------------------------------------------------------------------
# batches and caches
# ---------------------------------------------------------------------------

def batch_specs(cfg, batch, mesh):
    """Batch leaves (tokens / frames / vision_embeds): row-sharded over the
    batch axes, features replicated."""
    baxes = batch_axes_of(mesh)

    def leaf_spec(leaf) -> P:
        if not getattr(leaf, "ndim", 0):
            return P()
        return P(_fit(leaf.shape[0], mesh, baxes),
                 *([None] * (leaf.ndim - 1)))

    return jax.tree.map(leaf_spec, batch)


def cache_specs(cfg, cache, mesh):
    """KV / recurrent cache leaves: batch-sharded rows; attention KV pages
    additionally shard the kv-head axis over ``model`` (GQA); MLA latent
    caches ``(B, L, kv_lora_rank)`` keep the latent dim replicated — it is
    shared across heads by construction.

    Handles both LM caches (``prefix`` unstacked + ``blocks`` with a leading
    scan axis) and enc-dec caches (every leaf stacked over layers).
    """
    baxes = batch_axes_of(mesh)

    def leaf_spec(path, leaf) -> P:
        names = _path_names(path)
        stacked = 1 if (cfg.is_encdec or "blocks" in names) else 0
        body = tuple(leaf.shape[stacked:])
        entries = [_fit(body[0], mesh, baxes)] + [None] * (len(body) - 1)
        if names[-1] in ("k", "v", "ck", "cv") and len(body) == 4:
            entries[2] = _fit(body[2], mesh, MODEL_AXIS)   # kv-head axis
        return _with_lead(entries, stacked)

    return jax.tree_util.tree_map_with_path(leaf_spec, cache)
