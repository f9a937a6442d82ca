"""Weights made on the device from ``--seed``, in one jitted call.

The benchmark makes the weights itself, so that the plain reference can
take the same arrays without taking anything the program made. The layout
is the program's parameter tree (``LM.init``), read as shapes only through
``jax.eval_shape``; every value is drawn here:

- ``embed``: normal x ``embed_std`` (the LM head is tied to it);
- a norm's ``scale``: normal x ``norm_scale_std``; the program applies an
  RMSNorm weight as ``1 + scale``, and the reference does the same;
- every linear ``w`` of shape ``(..., d_in, d_out)``: normal / sqrt(d_in).
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp


def _std(path, shape, init: Dict[str, Any]) -> float:
    names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
    if names[-1] == "embed":
        return float(init["embed_std"])
    if names[-1] == "scale":
        return float(init["norm_scale_std"])
    if names[-1] == "w":
        return 1.0 / math.sqrt(shape[-2])
    raise ValueError(f"no initialisation rule for parameter {names}")


def make(model, seed31: int, dtype, init: Dict[str, Any]):
    """Parameters of ``model`` (an ``LM``) in its own tree layout; linear
    weights and the embedding in ``dtype``, norms as the layout says."""
    shapes = jax.eval_shape(lambda k: model.init(k, dtype),
                            jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    rules = [(_std(path, s.shape, init), s.shape, s.dtype)
             for path, s in flat]

    @jax.jit
    def draw(key):
        leaves = [(jax.random.normal(jax.random.fold_in(key, i), shape,
                                     jnp.float32) * std).astype(dt)
                  for i, (std, shape, dt) in enumerate(rules)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.block_until_ready(draw(jax.random.PRNGKey(seed31)))
