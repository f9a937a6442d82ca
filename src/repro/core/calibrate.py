"""Calibration: stream per-layer activation statistics into R factors.

The paper's memory story (§4.2): the calibration matrix X (n × tokens) can be
tens of GB, so we never materialize it. Each target linear layer owns an
``RStreamer`` — every captured activation chunk folds into a running n×n R
via TSQR ([R; chunkᵀ] → QR). The Gram accumulator (for the SVD-LLM baselines)
streams the same way via the Pallas ``gram_accum`` kernel.

On a mesh, the per-shard R factors combine with the butterfly
``distributed_tsqr_r`` (see core/tsqr.py) — calibration activations are
born sharded over the data axis and the tree never gathers them.
"""
from __future__ import annotations

from typing import Dict, Iterable, Optional

import jax
import jax.numpy as jnp

from repro.core.precision import highest_precision
from repro.core.tsqr import RStreamer, square_r
from repro.kernels import ops as kops
from repro.models.linear import CaptureDict
from repro.obs import trace


class Calibrator:
    """Capture sink + R accumulator. Use via ``model.capture_forward``."""

    def __init__(self, *, collect_gram: bool = False, dtype=jnp.float32,
                 max_tokens_per_record: int = 8192):
        self.streams: Dict[str, RStreamer] = {}
        self.grams: Dict[str, jax.Array] = {}
        self.collect_gram = collect_gram
        self.dtype = dtype
        self.max_tokens = max_tokens_per_record

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
        flat = jnp.asarray(x, self.dtype).reshape(-1, n)
        with trace.span("calib.record", path=path, tokens=flat.shape[0]):
            if path not in self.streams:
                self.streams[path] = RStreamer(n, self.dtype)
            # fold in manageable chunks (bounds the QR stack size)
            for i in range(0, flat.shape[0], self.max_tokens):
                self.streams[path].update(flat[i:i + self.max_tokens])
            if self.collect_gram:
                g = kops.gram_accum(flat)
                self.grams[path] = g if path not in self.grams \
                    else self.grams[path] + g

    def reset(self) -> None:
        """Drop every accumulated stream and Gram, keeping the capture
        wiring intact — a rolling traffic window (serve/recalibrate.py)
        starts its next window on the same instance."""
        self.streams.clear()
        self.grams.clear()

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
