"""Plain reference of calibration: the Gram matrix XᵀX of every target
linear's input, summed over calibration batches, from the float32
reference forward pass.

COALA's streaming QR keeps R with RᵀR = XᵀX, summed over every row folded
in, so the Gram of the batches folded in a window is what the R factors
must have gained over it. Each layer gives four inputs: the attention
input (read by the query, key and value projections), the attention output
(read by the output projection), the MLP input (gate and up) and the MLP
hidden state (down).

``quant=None`` is the reference, in float32 at ``"highest"``. The control
(``folds``) puts the reference in the program's place one precision lower:
every matmul of the forward pass computed as float32 at ``"high"`` does
(``quant="bf16x3"``: three bfloat16 products), its activations folded into
the R factors by a plain streaming QR.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Iterable, List, Optional

import jax
import jax.numpy as jnp

from benchmarks.chip.reference import transformer as ref

TAPS = ("attn_in", "attn_out", "mlp_in", "mlp_hidden")
# which of the four inputs each target linear reads
TAP_OF = {"wq": "attn_in", "wk": "attn_in", "wv": "attn_in",
          "wo": "attn_out", "gate": "mlp_in", "up": "mlp_in",
          "down": "mlp_hidden"}


def layer_and_tap(path: str):
    """``blocks/<r>/sub0/mixer/wq`` -> (r, "attn_in")."""
    parts = path.split("/")
    if parts[0] != "blocks" or parts[-1] not in TAP_OF:
        raise ValueError(f"unexpected calibration path {path!r}")
    return int(parts[1]), TAP_OF[parts[-1]]


def _taps(arch_items, quant, lp, x):
    taps: Dict[str, jax.Array] = {}
    y = ref.layer(dict(arch_items), lp, x, quant=quant, taps=taps)
    return y, {k: v.reshape(-1, v.shape[-1]) for k, v in taps.items()}


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_grams(arch_items, quant, lp, x):
    with jax.default_matmul_precision("highest"):
        y, taps = _taps(arch_items, quant, lp, x)
        return y, {k: ref._ein("ti,tj->ij", a, a, quant)
                   for k, a in taps.items()}


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_taps(arch_items, quant, lp, x):
    with jax.default_matmul_precision("highest"):
        return _taps(arch_items, quant, lp, x)


@jax.jit
def _add(acc, g):
    return jax.tree.map(jnp.add, acc, g)


def grams(arch: Dict[str, Any], params, batches: Iterable,
          quant: Optional[str] = None) -> List[Dict[str, jax.Array]]:
    """Per layer, the Gram of each of its four linear inputs, summed over
    ``batches`` (token arrays of shape (B, T))."""
    items = tuple(sorted(arch.items()))
    out: List[Dict[str, jax.Array]] = []
    for tokens in batches:
        x = ref._embed(params["embed"], jnp.asarray(tokens, jnp.int32))
        for i in range(arch["layers"]):
            x, g = _layer_grams(items, quant, ref._layer_params(params, i), x)
            if len(out) <= i:
                out.append(g)
            else:
                out[i] = _add(out[i], g)
    return out


@functools.partial(jax.jit, static_argnums=(2,))
def _fold(r, x, rows: int):
    """R of [r; x] by QR, ``rows`` rows of x at a time."""
    with jax.default_matmul_precision("highest"):
        for i in range(0, x.shape[0], rows):
            r = jnp.linalg.qr(jnp.concatenate([r, x[i:i + rows]]),
                              mode="r")
    return r


def folds(arch: Dict[str, Any], params, batches: Iterable,
          r_before: Dict[str, jax.Array], quant: str, rows: int
          ) -> Dict[str, jax.Array]:
    """The control: each target linear's R after folding the activations
    of ``batches`` from the forward pass in precision ``quant`` into
    ``r_before``, ``rows`` rows at a time."""
    items = tuple(sorted(arch.items()))
    by_layer: Dict[int, List[str]] = {}
    for path in r_before:
        by_layer.setdefault(layer_and_tap(path)[0], []).append(path)
    r = dict(r_before)
    for tokens in batches:
        x = ref._embed(params["embed"], jnp.asarray(tokens, jnp.int32))
        for i in range(arch["layers"]):
            x, taps = _layer_taps(items, quant, ref._layer_params(params, i),
                                  x)
            for path in by_layer.get(i, []):
                r[path] = _fold(r[path], taps[layer_and_tap(path)[1]], rows)
    return r
