"""Matmul precision of calibration and compression.

On a TPU, a float32 matmul at JAX's default precision runs as a single
bfloat16 pass: about three significant digits. That is the precision loss
COALA exists to avoid, so every entry point of calibration and compression
runs at ``"highest"`` (full float32): the forward pass that captures the
activations, the QR that folds them into R, and the SVD solve. At the
default precision the captured activations themselves depend on the batch
shape (a rounding flip in one layer is a bf16 ulp in the next), which
breaks the shard-count invariance of sharded calibration.

The scope sits once on each entry point: ``calibrate_model``,
``calibrate_sharded``, ``TrafficCalibrator.capture``, ``compress_model``,
and the public solves of ``core/coala.py``; what they call runs inside it.
Serving never runs in this scope: the engine's bf16 matmuls keep the
default. With calibration from traffic on, ``TrafficCalibrator.capture``
replays a sampled request's tokens through the capture forward at
admission, on the serving thread; only that replay is in the scope, the
engine's own jitted step is not.

``fn.__wrapped__`` is the same function without the scope, for measuring
what the default precision costs.
"""
from __future__ import annotations

import functools

import jax


def highest_precision(fn):
    """Run ``fn`` with ``jax.default_matmul_precision("highest")``."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped
