"""Calibration: stream per-layer activation statistics into R factors.

The paper's memory story (§4.2): the calibration matrix X (n × tokens) can be
tens of GB, so we never materialize it. Each target linear layer owns an
``RStreamer`` — every captured activation chunk folds into a running n×n R
via TSQR ([R; chunkᵀ] → QR). The Gram accumulator (for the SVD-LLM baselines)
streams the same way via the Pallas ``gram_accum`` kernel.

Linears that read one activation (q/k/v, gate/up, cross-attention k/v)
fold the same rows into the same starting state, which gives the same R
bit for bit. The calibrator remembers its last fold and hands its result
to the next record of that very array from that very state, so each
shared input is folded once per batch while every path keeps its own
stream.

A MoE layer's routed experts arrive together (``record_experts``): each
held expert's rows, zero-padded to one chunk size, stacked over the
experts. One jitted program folds the whole stack, vmapped over the
experts, each from its own R (a zero R for a stream with no rows yet), so
the fold compiles once per chunk size and width, whatever the counts.
Each expert keeps its own stream and true ``tokens_seen``.

On a mesh, the per-shard R factors combine with the butterfly
``distributed_tsqr_r`` (see core/tsqr.py) — calibration activations are
born sharded over the data axis and the tree never gathers them.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Iterable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.precision import highest_precision
from repro.core.tsqr import RStreamer, calib_fold_group, square_r
from repro.kernels import ops as kops
from repro.models.linear import CaptureDict
from repro.obs import trace


class _Fold(NamedTuple):
    """The calibrator's last fold: the input array (held, so that ``is``
    cannot match a new array at a reused address), the stream state it
    started from and the state it produced, and its Gram."""
    x: Any
    before: Tuple[Optional[jax.Array], int]
    after: Tuple[Optional[jax.Array], int]
    gram: Optional[jax.Array]


class Calibrator:
    """Capture sink + R accumulator. Use via ``model.capture_forward``."""

    def __init__(self, *, collect_gram: bool = False, dtype=jnp.float32,
                 max_tokens_per_record: int = 8192):
        self.streams: Dict[str, RStreamer] = {}
        self.grams: Dict[str, jax.Array] = {}
        self.collect_gram = collect_gram
        self.dtype = dtype
        self.max_tokens = max_tokens_per_record
        self._last: Optional[_Fold] = None
        self._fold_group = jax.jit(calib_fold_group)
        self._zero_r: Dict[int, jax.Array] = {}

    # ------------------------------------------------------------ capture
    def wrap(self, block_params, path: str):
        """Recursively wrap every linear-layer dict {'w': ...} — and MoE
        expert banks ('w_gate' dicts, captured per-expert) — for capture."""
        def walk(node, p):
            if isinstance(node, dict):
                if "w" in node and getattr(node["w"], "ndim", 0) == 2:
                    cd = CaptureDict(node)
                    cd.path = p
                    cd.calib = self
                    return cd
                inner = {k: walk(v, f"{p}/{k}") for k, v in node.items()}
                if "w_gate" in node:       # MoE layer: per-expert capture
                    cd = CaptureDict(inner)
                    cd.path = p
                    cd.calib = self
                    return cd
                return inner
            if isinstance(node, list):
                return [walk(v, f"{p}/{i}") for i, v in enumerate(node)]
            return node
        return walk(block_params, path)

    def record(self, path: str, x: jax.Array):
        n = x.shape[-1]
        if path not in self.streams:
            self.streams[path] = RStreamer(n, self.dtype)
        stream = self.streams[path]
        before = stream.state
        last = self._last
        shared = (last is not None and last.x is x
                  and before[0] is last.before[0]
                  and before[1] == last.before[1])
        with trace.span("calib.record", path=path,
                        tokens=math.prod(x.shape[:-1]), shared=shared):
            if shared:
                # the same rows folded into the same state: the same bits
                stream.state = last.after
                g = last.gram
            else:
                flat = jnp.asarray(x, self.dtype).reshape(-1, n)
                # fold in manageable chunks (bounds the QR stack size)
                for i in range(0, flat.shape[0], self.max_tokens):
                    stream.update(flat[i:i + self.max_tokens])
                g = kops.gram_accum(flat) if self.collect_gram else None
                self._last = _Fold(x, before, stream.state, g)
            if g is not None:
                self.grams[path] = g if path not in self.grams \
                    else self.grams[path] + g

    def record_experts(self, path: str, e_start: int,
                       stacks: List[Tuple[jax.Array, jax.Array]],
                       counts: np.ndarray) -> None:
        """A MoE layer's held experts ``e_start + e``: per chunk, the input
        (E, rows, d) and hidden (E, rows, f) stacks, expert ``e``'s
        ``counts[e]`` routed rows first in its slots and zeros after them.
        Each (chunk, tap) is one grouped fold; an expert whose chunk holds
        no rows keeps its R as it is."""
        counts = np.asarray(counts)
        for j, pair in enumerate(stacks):
            for tap, stack in zip(("in", "hid"), pair):
                e_count, rows, n = stack.shape
                got = np.clip(counts - j * rows, 0, rows)
                names = [f"{path}/expert{e_start + e}/{tap}"
                         for e in range(e_count)]
                for name, k in zip(names, got):
                    if k and name not in self.streams:
                        self.streams[name] = RStreamer(n, self.dtype)
                live = [self.streams.get(name) for name in names]
                if n not in self._zero_r:
                    self._zero_r[n] = jnp.zeros((n, n), self.dtype)
                zero = self._zero_r[n]
                rs = tuple(zero if s is None or s.state[0] is None
                           else s.state[0] for s in live)
                with trace.span("calib.expert_fold", path=f"{path}/{tap}",
                                experts=e_count, rows=int(got.sum()),
                                padded_rows=e_count * rows) as sp:
                    new = self._fold_group(rs, stack.astype(self.dtype))
                    for s, r, k in zip(live, new, got):
                        if k:
                            s.state = (r, s.tokens_seen + int(k))
                    sp.set(underfilled=sum(
                        s is not None and s.tokens_seen < n for s in live))

    def reset(self) -> None:
        """Drop every accumulated stream and Gram, keeping the capture
        wiring intact — a rolling traffic window (serve/recalibrate.py)
        starts its next window on the same instance."""
        self.streams.clear()
        self.grams.clear()
        self._last = None

    # ------------------------------------------------------------ results
    def r_factors(self) -> Dict[str, jax.Array]:
        return {p: square_r(s.r) for p, s in self.streams.items()}

    def tokens_seen(self) -> Dict[str, int]:
        return {p: s.tokens_seen for p, s in self.streams.items()}


@highest_precision
def calibrate_model(model, params, batches: Iterable[dict], *,
                    collect_gram: bool = False) -> Calibrator:
    """Run capture over calibration batches; returns the filled Calibrator."""
    cal = Calibrator(collect_gram=collect_gram)
    for batch in batches:
        model.capture_forward(params, batch, cal)
    return cal
