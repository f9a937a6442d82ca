"""Live-traffic recalibration: stream serving activations back into COALA
and hot-swap refreshed factors into a running engine without draining.

The paper's scenario (3) — insufficient calibration data — comes with
explicit error bounds, which means a *running server* can know when
traffic-derived calibration has seen enough tokens to produce a
trustworthy approximation. This module closes that loop:

  * ``TrafficCalibrator`` duck-types (subclasses) ``core.calibrate.
    Calibrator``: a sampled fraction of requests have their served token
    streams replayed through the model's unrolled-eager capture path
    (``LM.capture_prefill``) into the same per-layer streaming-R
    accumulators offline calibration uses — so ``compress_model`` /
    ``compress_model_pair`` and the ``obs.numerics`` monitors work
    unchanged. Each served position is captured exactly once: the prompt
    at admission, the generated inputs at completion (causality makes the
    position-sliced replay the exact activations serving computed), so
    the traffic R equals an offline ``Calibrator`` fed the same streams
    as RᵀR up to TSQR chunk-order roundoff (tests/test_compress.py pins
    that invariance; benchmarks gate the parity).

  * ``RecalibWorker`` watches the three numerics grades — data volume,
    conditioning, residual-vs-bound — and recompresses once the *bound
    clears* the policy:

      1. **data**: every target layer has streamed ``min_token_factor × n``
         tokens. The default (0.25) sits deliberately below the offline
         monitors' factor of 1.0: the μ-regularized solve is exactly the
         paper's cure for the under-streamed regime, so the worker does
         not wait for full-rank data — the remaining gates decide.
      2. **conditioning**: no layer's μ-augmented R̃ (the factor the
         Prop. 3 solve actually uses; ``obs.numerics.
         check_augmented_r_factors``) grades FAIL.
      3. **bound**: every recompressed layer's achieved residual
         ``‖(W−W')R̃ᵀ‖/‖WR̃ᵀ‖`` is within ``max_residual_excess`` of the
         attainable Σ-tail bound (``obs.numerics.check_compression``) —
         a solver that silently lost accuracy never ships.

    Ranks are pinned from the serving factors' original compression
    (``core.compress.rank_map_from_reports``), so the refreshed pytree
    has identical treedef/shapes/dtypes and ``ContinuousEngine.
    hot_swap`` is a pure value swap: params are traced jit *arguments*
    (never donated), the existing cache entries hit, and
    ``post_warmup_compiles`` stays 0 across a swap. In-flight requests
    keep their KV pages and continue token-exactly on the new factors'
    forward pass — swapping identical values is asserted to be a perfect
    no-op (tests/test_recalibrate.py, tests/test_soak_serve.py).

The worker runs inline by default — ``on_step`` polls the gates between
engine steps, deterministic and test-friendly. ``async_solve=True`` moves
the solve to a background thread that *stages* the params; the engine
applies the staged swap at the top of its next ``step()``, so the swap
still lands between steps, never mid-dispatch.
"""
from __future__ import annotations

import math
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from repro.core.calibrate import Calibrator
from repro.core.compress import compress_model
from repro.core.precision import highest_precision
from repro.obs import numerics, trace

FAIL = numerics.FAIL


@dataclass(frozen=True)
class RecalibPolicy:
    """When is traffic-derived calibration trustworthy enough to ship?

    ``min_token_factor`` is the *data* gate (tokens per layer >= factor ×
    features); 0.25 by default — deliberately below the offline monitors'
    1.0 because the μ-regularized solve is well-posed under partial data
    (Prop. 3 is the paper's cure for exactly this regime) and the
    conditioning + residual-vs-bound gates do the real vetting. A swap
    is attempted at most every ``check_every`` engine steps, and after a
    swap (or a failed bound check) only once ``min_new_tokens`` fresh
    tokens have streamed in."""
    sample_rate: float = 1.0        # fraction of requests captured
    min_token_factor: float = 0.25  # data gate: tokens >= factor * n
    max_residual_excess: float = 2.0  # bound gate: residual <= excess * bound
    fail_cond: float = 1e8          # conditioning gate on μ-augmented R̃
    check_every: int = 2            # poll cadence, in engine steps
    min_new_tokens: int = 32        # fresh tokens between solve attempts
    capture_generated: bool = True  # replay generated inputs at completion


class TrafficCalibrator(Calibrator):
    """``Calibrator`` fed by live traffic instead of a calibration set.

    Capture is incremental and exactly-once per served position: a sampled
    request's prompt is replayed at admission and its generated *inputs*
    (every emitted token except the last, which no forward pass consumed)
    at completion, each time recording only positions not yet captured.
    The position slicing lives in the ``record`` override so the model's
    capture path stays byte-identical to offline calibration."""

    def __init__(self, model, *, ctx=None, policy: RecalibPolicy = None,
                 dtype=None, compute_dtype=None, seed: int = 0):
        import jax.numpy as jnp
        from repro.models.common import CPU_CTX
        super().__init__(dtype=dtype or jnp.float32)
        self.model = model
        self.ctx = CPU_CTX if ctx is None else ctx
        self.policy = policy or RecalibPolicy()
        self.compute_dtype = compute_dtype or jnp.float32
        self._rng = np.random.RandomState(seed)
        self._rec_start = 0
        # req_id -> number of stream positions captured so far; sampling is
        # sticky (a request is in or out for its whole lifetime)
        self._sampled: Dict[int, int] = {}
        self._rejected: set = set()
        self.sampled_requests = 0
        self.captured_tokens = 0
        # full streams captured from finished requests, for offline-parity
        # replay (benchmarks/run.py feeds these to a plain Calibrator)
        self.captured_streams: List[np.ndarray] = []

    # ------------------------------------------------------------ capture
    def record(self, path: str, x) -> None:
        if self._rec_start and getattr(x, "ndim", 2) >= 3:
            x = x[:, self._rec_start:]
        super().record(path, x)

    @highest_precision
    def capture(self, base_params, tokens, *, start: int = 0) -> None:
        """Replay ``tokens`` (T,) through the eager capture path, recording
        only positions >= ``start`` (each conditioned on its full prefix)."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if len(tokens) <= start:
            return
        with trace.span("serve.recalib_capture", tokens=len(tokens) - start,
                        start=start):
            self._rec_start = start
            try:
                self.model.capture_prefill(base_params, tokens, self,
                                           ctx=self.ctx,
                                           compute_dtype=self.compute_dtype)
            finally:
                self._rec_start = 0
        self.captured_tokens += len(tokens) - start

    def _admit(self, req_id: int) -> bool:
        if req_id in self._sampled:
            return True
        if req_id in self._rejected:
            return False
        if self._rng.random_sample() < self.policy.sample_rate:
            self._sampled[req_id] = 0
            self.sampled_requests += 1
            return True
        self._rejected.add(req_id)
        return False

    def on_prefill(self, base_params, req) -> None:
        """Admission-time capture of the tokens this prefill computes over
        (prompt, or prompt + generated-so-far for a resumed preemptee)."""
        if not self._admit(req.req_id):
            return
        stream = np.asarray(req.prefill_tokens(), np.int32)
        done = self._sampled[req.req_id]
        self.capture(base_params, stream, start=done)
        self._sampled[req.req_id] = max(done, len(stream))

    def on_finish(self, base_params, req) -> None:
        """Completion-time capture of the generated inputs (everything the
        decode loop fed back in: ``out_tokens[:-1]``)."""
        done = self._sampled.pop(req.req_id, None)
        self._rejected.discard(req.req_id)
        if done is None:
            return
        stream = np.concatenate(
            [np.asarray(req.prompt, np.int32),
             np.asarray(req.out_tokens[:-1], np.int32)])
        if self.policy.capture_generated and len(stream) > done:
            self.capture(base_params, stream, start=done)
            done = len(stream)
        self.captured_streams.append(stream[:done])


class RecalibWorker:
    """Watches the numerics gates over a ``TrafficCalibrator`` and hot-swaps
    recompressed factors into a live ``ContinuousEngine``.

    Attach with ``engine.attach_recalibrator(worker)``; the engine then
    calls ``on_prefill`` / ``on_finish`` on the capture path and
    ``on_step`` at the top of every ``step()`` (which applies staged swaps
    and, in inline mode, polls the gates)."""

    def __init__(self, model, base_params, cal: TrafficCalibrator, ccfg, *,
                 rank_map: Dict[str, int],
                 draft_ratio: float = 0.0,
                 draft_rank_map: Optional[Dict[str, int]] = None,
                 async_solve: bool = False):
        if not rank_map:
            raise ValueError("rank_map is empty: nothing to recompress "
                             "(pin it from the initial compression's "
                             "reports via rank_map_from_reports)")
        self.model = model
        self.base_params = base_params
        self.cal = cal
        self.ccfg = ccfg
        self.rank_map = dict(rank_map)
        self.draft_ratio = float(draft_ratio)
        self.draft_rank_map = dict(draft_rank_map) if draft_rank_map else None
        if self.draft_ratio > 0 and not self.draft_rank_map:
            raise ValueError("draft recompression needs draft_rank_map")
        self.policy = cal.policy
        self.async_solve = async_solve
        # observable state
        self.swaps = 0
        self.solve_attempts = 0
        self.last_status = "collecting"
        self.last_excess = float("nan")
        self.last_swap_seconds = float("nan")
        self.tokens_at_first_swap: Optional[int] = None
        self._steps = 0
        self._tokens_at_last_solve = -(10 ** 9)
        self._staged = None
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._metrics = {}
        # set by engine.attach_recalibrator: lets the async solve path (no
        # engine argument) reach the flight recorder / postmortem dump when
        # a readiness gate rejects a solve
        self._engine = None

    # ------------------------------------------------------------ metrics
    def bind_metrics(self, **counters) -> None:
        """Engine-owned ``serve_recalib_*`` counters the worker increments
        (``attach_recalibrator`` wires them up)."""
        self._metrics = counters

    def _inc(self, name: str, by=1) -> None:
        c = self._metrics.get(name)
        if c is not None:
            c.inc(by)

    # ------------------------------------------------------------ hooks
    def on_prefill(self, engine, req) -> None:
        before_r, before_t = self.cal.sampled_requests, self.cal.captured_tokens
        self.cal.on_prefill(self.base_params, req)
        self._inc("sampled", self.cal.sampled_requests - before_r)
        self._inc("tokens", self.cal.captured_tokens - before_t)
        self._record_capture(engine, req, self.cal.captured_tokens - before_t,
                             at="prefill")

    def on_finish(self, engine, req) -> None:
        before_t = self.cal.captured_tokens
        self.cal.on_finish(self.base_params, req)
        self._inc("tokens", self.cal.captured_tokens - before_t)
        self._record_capture(engine, req, self.cal.captured_tokens - before_t,
                             at="finish")

    @staticmethod
    def _record_capture(engine, req, tokens: int, *, at: str) -> None:
        fl = getattr(engine, "flight", None)
        if fl is not None and tokens > 0:
            fl.record("recalib_capture", req_id=req.req_id,
                      tokens=int(tokens), at=at)

    def on_step(self, engine) -> None:
        """Between-steps hook: apply any staged swap, then (inline mode)
        poll the gates every ``check_every`` steps; in async mode kick the
        solver thread instead so ``step()`` never blocks on a solve."""
        self._steps += 1
        with self._lock:
            staged, self._staged = self._staged, None
        if staged is not None:
            self._apply(engine, *staged)
        if self._steps % max(self.policy.check_every, 1) != 0:
            return
        if self.async_solve:
            if (self._thread is None or not self._thread.is_alive()) \
                    and self._should_solve():
                self._thread = threading.Thread(
                    target=self._solve_and_stage, daemon=True)
                self._thread.start()
        else:
            self.poll(engine)

    # ------------------------------------------------------------ gates
    def min_tokens_seen(self) -> int:
        seen = self.cal.tokens_seen()
        return min((seen.get(p, 0) for p in self.rank_map), default=0)

    def clearance(self) -> float:
        """min over target layers of tokens_seen / (min_token_factor × n):
        the data gate clears at >= 1.0. Layers with no stream yet pin 0."""
        seen = self.cal.tokens_seen()
        dims = {p: int(r.shape[0]) for p, r in self.cal.r_factors().items()}
        worst = math.inf
        for p in self.rank_map:
            if p not in dims:
                return 0.0
            need = self.policy.min_token_factor * dims[p]
            worst = min(worst, seen.get(p, 0) / max(need, 1e-9))
        return 0.0 if worst is math.inf else float(worst)

    def _should_solve(self) -> bool:
        if self.clearance() < 1.0:
            self.last_status = "collecting"
            return False
        if (self.cal.captured_tokens - self._tokens_at_last_solve
                < self.policy.min_new_tokens):
            return False
        return True

    # ------------------------------------------------------------ solve/swap
    def poll(self, engine) -> bool:
        """Inline gate check + solve + swap; returns True if a swap landed."""
        if not self._should_solve():
            return False
        result = self._solve()
        if result is None:
            return False
        self._apply(engine, *result)
        return True

    def _solve_and_stage(self) -> None:
        result = self._solve()
        if result is not None:
            with self._lock:
                self._staged = result

    def _solve(self):
        """Recompress against the traffic R factors and vet the result;
        returns (params, draft_params) or None when a gate fails."""
        import dataclasses as dc
        self.solve_attempts += 1
        self._tokens_at_last_solve = self.cal.captured_tokens
        with trace.span("serve.recalib_solve",
                        tokens=self.cal.captured_tokens):
            new_params, reports = compress_model(
                self.model, self.base_params, self.cal, self.ccfg,
                rank_map=self.rank_map)
            draft_params = None
            if self.draft_ratio > 0:
                dcfg = dc.replace(self.ccfg, ratio=self.draft_ratio, rank=0)
                draft_params, _ = compress_model(
                    self.model, self.base_params, self.cal, dcfg,
                    rank_map=self.draft_rank_map)
        with trace.span("serve.recalib_check"):
            pol = numerics.NumericsPolicy(
                fail_cond=self.policy.fail_cond,
                min_token_factor=self.policy.min_token_factor,
                warn_residual_excess=self.policy.max_residual_excess,
                fail_residual_excess=self.policy.max_residual_excess)
            mus = {r.path: r.mu for r in reports}
            target_rf = {p: r for p, r in self.cal.r_factors().items()
                         if p in self.rank_map}
            conds = numerics.check_augmented_r_factors(
                target_rf, mus, self.cal.tokens_seen(), pol)
            comp = numerics.check_compression(reports, pol)
            excesses = [h.residual / max(h.bound, 1e-12) for h in comp]
            self.last_excess = max(excesses) if excesses else float("nan")
            cond_fail = [h for h in conds
                         if not math.isfinite(h.cond)
                         or h.cond >= self.policy.fail_cond]
            bound_fail = [h for h in comp if h.level == FAIL]
        if cond_fail or bound_fail:
            self.last_status = ("cond_fail" if cond_fail else "bound_fail")
            trace.instant("serve.recalib_reject", status=self.last_status,
                          layers=len(cond_fail) + len(bound_fail))
            # a gate rejection means the numerics monitors graded the solve
            # untrustworthy — exactly the moment the postmortem bundle is
            # worth having (engine/flight wiring is optional; no-op without)
            eng = self._engine
            fl = getattr(eng, "flight", None)
            if fl is not None:
                fl.record("recalib_reject", status=self.last_status,
                          layers=len(cond_fail) + len(bound_fail),
                          excess=float(self.last_excess)
                          if math.isfinite(self.last_excess) else None)
                eng.dump_postmortem(f"recalib_{self.last_status}")
            return None
        self.last_status = "cleared"
        return new_params, draft_params

    def _apply(self, engine, new_params, draft_params) -> None:
        t0 = time.perf_counter()
        engine.hot_swap(new_params, draft_params)
        self.last_swap_seconds = time.perf_counter() - t0
        self.swaps += 1
        self._inc("swaps")
        if self.tokens_at_first_swap is None:
            self.tokens_at_first_swap = self.cal.captured_tokens
        self.last_status = "swapped"

    def summary(self) -> Dict[str, float]:
        return {
            "swaps": self.swaps,
            "solve_attempts": self.solve_attempts,
            "sampled_requests": self.cal.sampled_requests,
            "captured_tokens": self.cal.captured_tokens,
            "clearance": self.clearance(),
            "residual_excess": self.last_excess,
            "status": self.last_status,
        }
