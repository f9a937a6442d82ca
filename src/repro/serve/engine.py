"""Serving engines.

``ServeEngine`` — the original fixed-batch loop: one synchronized batch, a
dense monolithic KV cache, everything decodes in lockstep. Kept as the
fallback/oracle path.

``ContinuousEngine`` — request-level continuous batching over a paged KV
cache. ``submit()`` enqueues a request; each ``step()`` admits whatever fits
(scheduler + block pool), prefills joiners into pool blocks, then runs ONE
decode step over the whole running set at per-request positions (the
models' vector-``pos`` decode path), so requests of different lengths
interleave freely and finished requests free their blocks immediately.
Per-request sampling params (greedy + temperature) are applied row-wise;
sampling keys are folded per (seed, output index) so a preempted request
resumes on the same trajectory.

Prefill path (pure-attention LMs): admission looks up the longest cached
block-aligned prefix in the pool's prefix registry (``prefix_cache``,
auto-on; token-exact intern chains over full blocks) and only the *suffix*
is computed; joiners whose suffixes land in the same length bucket
(``prefill_bucket_sizes``, default powers of two with floor 8) prefill
together in ONE jitted ``LM.prefill_chunk`` call at per-row cache offsets
— so prefill compiles per (batch, length, blocks) bucket instead of per
prompt length (``metrics()["prefill_compiles"]``). By default
(``prefill_kernel=True`` where the model supports it) that call runs the
chunked-prefill kernel (``kernels/chunked_prefill.py``) directly against
the pool's page stores with the per-request block tables: attention
scatters the suffix K/V into its pages and attends through the table
indirection with per-row prefix-offset causal masks — no gather or
scatter of the cache; the donated stores flow back via ``absorb_paged``.
``prefill_kernel=False`` keeps the gather-into-contiguous path as the
in-tree oracle. ``fork()`` clones a running request copy-on-write for
best-of-n sampling. Models with extras (whisper frames, VLM vision
prefixes) and recurrent/hybrid archs keep the legacy per-request prefill.

Decode read path: by default (``paged_kernel=True`` where the model
supports it) each step passes the pool's page stores *directly* into the
jitted ``decode_step`` together with the per-request block tables — the
attention layers resolve the indirection in-kernel
(``kernels/paged_attention.py``) and write the new token into its page, so
no contiguous copy of the KV history is ever materialized and the updated
page stores flow straight back into the pool (``absorb_paged`` swaps array
references; the cache argument is donated so XLA updates pages in place).
The legacy gather path (``paged_kernel=False``) assembles the contiguous
pytree ``init_cache`` would have produced and remains the oracle — under
greedy decoding both are token-identical to ``ServeEngine``
(tests/test_serve_continuous.py asserts this).

Shape buckets: the decode batch is padded to the next size in
``bucket_sizes`` and the block envelope to the next power of two, so
``step()`` hits a small closed set of jit signatures instead of recompiling
every time traffic shifts; ``metrics()["decode_compiles"]`` exposes the
compile-cache counter that tests/test_serve_buckets.py guards. Padding rows
read/write the pool's trash page and trash state slot.

Warm start: because decode pads to shape buckets and prefill to
(batch, length, blocks) buckets, the set of jit signatures any admissible
trace can hit is *closed and enumerable* — ``warmup(max_len=...)``
enumerates exactly that set (``warmup_signatures``) and executes every
signature once against the pool's trash page before traffic arrives, so
the first request's TTFT equals steady-state TTFT and
``metrics()["post_warmup_compiles"]`` stays 0 under any traffic whose
per-request cache need fits ``max_len`` (tests/test_warmup.py asserts
``== 0``, not ``≤ buckets``). The pool pre-compiles its own maintenance
jits (block zeroing, COW copy) in the same pass.

Async host pipeline: per-token host work — detokenizing and the user's
``stream_callback`` — runs on a background worker thread fed by a FIFO
queue (``serve/detokenize.py``), so ``step()`` returns as soon as the next
device step is dispatched. ``async_detok=False`` keeps the inline
synchronous path as the ordering/parity oracle; ``run()`` flushes the
worker before returning.

Offline lane: ``run_offline(requests)`` is the MLPerf-style
throughput-bound mode — sort by prompt length so same-bucket prompts are
admitted together and pack into shared bucketed prefill calls, drive to
drain, return results in input order.

Speculative decoding (``draft_params=...``): the engine serves the target
model and a COALA-compressed draft of it side by side, each against its
own paged pool (identical geometry — compression only changes weights).
Every decode round, one jitted ``lax.scan`` over ``spec_k + 1`` draft
steps proposes ``spec_k`` tokens per request (sampling in-scan, so the
whole proposal costs a single dispatch), then the target scores all
``spec_k + 1`` positions in one ``verify_chunk`` call riding the PR-4
L-token paged write path. Greedy rows accept the longest prefix of
proposals matching the target argmax (token-exact vs the non-speculative
engine by induction); temperature rows run standard rejection sampling
(accept ``d_i`` w.p. ``min(1, p/q)``, residual draw from
``norm(max(p-q, 0))``, bonus draw after a full accept). Rejected tail
pages are rolled back via ``BlockPool.truncate``; acceptance is exported
as ``serve_spec_*`` counters and ``metrics()["spec_accept_rate"]``.

docs/serving.md documents the page/block/intern-chain/bucket vocabulary,
the request data flow, the warmup lifecycle, and every CLI knob;
docs/kernels.md documents the decode and chunked-prefill kernels this
engine drives.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import CPU_CTX, ParallelCtx
from repro.models.transformer import LM, period_specs
from repro.obs import trace
from repro.obs.metrics import LATENCY_BUCKETS, Registry
from repro.serve.detokenize import DetokenizeWorker, deliver
from repro.serve.paged_cache import BlockPool
from repro.serve.scheduler import Request, Scheduler


def _pow2_at_least(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def default_bucket_sizes(max_running: int) -> tuple:
    """Power-of-two batch buckets covering [1, max_running]."""
    sizes = []
    b = 1
    while b < max_running:
        sizes.append(b)
        b *= 2
    return tuple(sizes) + (max_running,)


@dataclasses.dataclass
class ServeEngine:
    model: object
    params: object
    ctx: ParallelCtx = CPU_CTX
    compute_dtype: object = jnp.bfloat16
    cache_dtype: object = jnp.bfloat16

    def __post_init__(self):
        m, ctx, cd = self.model, self.ctx, self.compute_dtype
        self._prefill = jax.jit(
            lambda p, tk, c, **kw: m.prefill(p, tk, c, ctx=ctx,
                                             compute_dtype=cd, **kw))
        self._decode = jax.jit(
            lambda p, tk, c, pos: m.decode_step(p, tk, c, pos, ctx=ctx,
                                                compute_dtype=cd))

    def generate(self, prompt_tokens, max_new_tokens: int, *,
                 extras: Optional[Dict] = None, temperature: float = 0.0,
                 seed: int = 0, max_len: Optional[int] = None):
        """prompt_tokens: (B, T_prompt) int32 -> (B, T_prompt+new) int32."""
        b, t0 = prompt_tokens.shape
        kw = dict(extras or {})
        # vlm: the vision prefix occupies the first cache positions, so the
        # cache and the decode write positions are offset by its length
        vis = 0
        cfg = getattr(self.model, "cfg", None)
        if ("vision_embeds" in kw and cfg is not None
                and getattr(cfg, "family", "") == "vlm"):
            vis = kw["vision_embeds"].shape[1]
        total = max_len or (vis + t0 + max_new_tokens)
        cache = self.model.init_cache(b, total, dtype=self.cache_dtype)
        logits, cache = self._prefill(self.params, prompt_tokens, cache, **kw)
        logits = logits[:, -1] if logits.ndim == 3 else logits
        out = [prompt_tokens]
        key = jax.random.PRNGKey(seed)
        tok = self._sample(logits, temperature, key)
        for i in range(max_new_tokens):
            out.append(tok)
            if i == max_new_tokens - 1:
                break
            pos = jnp.asarray(vis + t0 + i, jnp.int32)
            logits, cache = self._decode(self.params, tok, cache, pos)
            key, sk = jax.random.split(key)
            tok = self._sample(logits, temperature, sk)
        return jnp.concatenate(out, axis=1)

    @staticmethod
    def _sample(logits, temperature, key):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        return jax.random.categorical(key, logits / temperature)[:, None] \
            .astype(jnp.int32)


def _sample_rows(logits, temps, keys):
    """Row-wise sampling: greedy where temp <= 0, categorical otherwise."""
    def one(lg, temp, key):
        greedy = jnp.argmax(lg, axis=-1)
        samp = jax.random.categorical(key, lg / jnp.maximum(temp, 1e-6))
        return jnp.where(temp > 0.0, samp, greedy).astype(jnp.int32)
    return jax.vmap(one)(logits, temps, keys)


# key-derivation fold tags decorrelating the speculative streams from the
# engine's per-(seed, output-index) decode keys and from each other
_DRAFT_FOLD = 0x0D1A           # in-scan draft proposal sampling
_ACCEPT_FOLD = 0xACC           # host-side accept/residual draws
_BONUS_FOLD = 0xB0E5           # host-side bonus draw after a full accept


def _softmax_np(x: np.ndarray) -> np.ndarray:
    x = x - np.max(x)
    e = np.exp(x)
    return e / e.sum()


class ContinuousEngine:
    """Request-level serving: ``submit()`` / ``step()`` / ``stream()``."""

    def __init__(self, model, params, *, ctx: ParallelCtx = CPU_CTX,
                 compute_dtype=jnp.bfloat16, cache_dtype=jnp.bfloat16,
                 block_size: int = 16, num_blocks: int = 512,
                 max_running: int = 8,
                 paged_kernel: Optional[bool] = None,
                 prefill_kernel: Optional[bool] = None,
                 paged_attn_impl: Optional[str] = None,
                 bucket_sizes: Optional[Sequence[int]] = None,
                 prefix_cache: Optional[bool] = None,
                 prefill_bucket_sizes: Optional[Sequence[int]] = None,
                 detokenizer: Optional[Callable[[int], str]] = None,
                 async_detok: Optional[bool] = None,
                 draft_params=None, spec_k: int = 4,
                 slo_ttft_s: Optional[float] = None,
                 slo_tpot_s: Optional[float] = None,
                 flight_recorder=None):
        self.model = model
        self.params = params
        # live-telemetry plane (docs/observability.md): an optional flight
        # recorder of per-request lifecycle events, per-request latency SLOs
        # feeding the goodput gauge (None = every request trivially meets
        # them), and the step/liveness bookkeeping /healthz reads
        self.flight = flight_recorder
        self.slo_ttft_s = slo_ttft_s
        self.slo_tpot_s = slo_tpot_s
        self._step_idx = 0
        self._swap_epoch = 0
        self.last_step_time: Optional[float] = None
        self.warmed = False
        if paged_attn_impl is not None:
            ctx = dataclasses.replace(ctx, paged_attn_impl=paged_attn_impl)
        self.ctx = ctx
        self.compute_dtype = compute_dtype
        self.cache_dtype = cache_dtype
        self.block_size = block_size
        # chunked (position-offset) prefill rides the vector-pos attention
        # path, so it needs a pure-attention LM: recurrent/hybrid layers
        # (mamba, xlstm) would need state snapshots at block boundaries
        chunk_ok = isinstance(model, LM)
        if chunk_ok:
            pre, per, _ = period_specs(model.cfg)
            chunk_ok = all(s.kind == "attn" for s in pre + per)
        self._chunk_ok = chunk_ok
        self.prefix_cache = chunk_ok if prefix_cache is None else prefix_cache
        if self.prefix_cache and not chunk_ok:
            raise ValueError(
                "prefix caching needs chunked suffix prefill, which this "
                "model does not support (recurrent/hybrid/enc-dec layers)")
        # speculative decoding: a (COALA-compressed) draft shares the target
        # model's architecture, so its paged pool has identical geometry and
        # the verifier is the chunked-prefill path scored at every position
        self.draft_params = draft_params
        self.spec_k = int(spec_k)
        self._spec = draft_params is not None
        if self._spec and not chunk_ok:
            raise ValueError(
                "speculative decoding needs the chunked (position-offset) "
                "prefill path as its verifier (pure-attention LM)")
        if self._spec and self.spec_k < 1:
            raise ValueError("spec_k must be >= 1")
        # one registry per engine: pool and scheduler register their own
        # series into it, metrics() is a compatibility view over it, and
        # launch/serve.py --metrics-out writes its Prometheus exposition
        self.registry = Registry()
        self.pool = BlockPool(model, num_blocks=num_blocks,
                              block_size=block_size,
                              max_requests=max_running, dtype=cache_dtype,
                              prefix_cache=self.prefix_cache,
                              registry=self.registry)
        self.scheduler = Scheduler(self.pool, max_running=max_running,
                                   registry=self.registry,
                                   headroom_tokens=self.spec_k
                                   if self._spec else 0,
                                   flight=flight_recorder)
        # the draft decodes against its own pool (private registry: the
        # engine registry's pool_* series describe the target pool), kept in
        # lockstep with the target's — same allocs, commits, forks, frees —
        # so cached-prefix hits and table shapes mirror exactly
        self.draft_pool = BlockPool(
            model, num_blocks=num_blocks, block_size=block_size,
            max_requests=max_running, dtype=cache_dtype,
            prefix_cache=self.prefix_cache) if self._spec else None
        # the paged read path needs attention layers that understand page
        # stores: decoder-only/VLM/hybrid LMs with plain GQA K/V caches
        # (MLA keeps latent caches; enc-dec models route through EncDecLM)
        supported = isinstance(model, LM) and not model.cfg.kv_lora_rank
        self.paged_kernel = supported if paged_kernel is None else paged_kernel
        if self.paged_kernel and not supported:
            raise ValueError(
                "paged decode kernel unsupported for this model (MLA/enc-dec)")
        # the chunked-prefill kernel needs both the chunked suffix-prefill
        # path (pure-attention LM) and page-store-aware attention (plain GQA
        # K/V caches, no MLA latents)
        prefill_supported = chunk_ok and supported
        self.prefill_kernel = (prefill_supported if prefill_kernel is None
                               else prefill_kernel)
        if self.prefill_kernel and not prefill_supported:
            raise ValueError(
                "chunked-prefill kernel unsupported for this model "
                "(recurrent/hybrid/MLA/enc-dec layers)")
        buckets = set(bucket_sizes or default_bucket_sizes(max_running))
        buckets.add(max_running)        # largest bucket must cover the batch
        self.bucket_sizes = tuple(sorted(buckets))
        self.prefill_bucket_sizes = tuple(sorted(prefill_bucket_sizes)) \
            if prefill_bucket_sizes else ()
        self.finished: List[Request] = []
        self._next_id = 0
        self._start_time: Optional[float] = None
        self._recalib = None            # attach_recalibrator() installs one
        self._decode_shapes: set = set()
        self._prefill_shapes: set = set()
        self._spec_shapes: set = set()          # draft-scan + verify rounds
        self._draft_prefill_shapes: set = set()  # prefill run with draft params
        # async host pipeline: detokenize + stream callbacks run on the
        # worker's thread (lazily started on first emission); off = inline
        # synchronous delivery, the ordering/parity oracle
        self.detokenizer = detokenizer
        self.async_detok = True if async_detok is None else async_detok
        self._detok = DetokenizeWorker(detokenizer) if self.async_detok \
            else None
        # warm-start bookkeeping: compile-cache sizes recorded when
        # warmup() finishes, so post_warmup_compiles() counts only jit
        # signatures traffic hit that warmup failed to cover
        self._warmup_seconds = 0.0
        self._warmed_decode = 0
        self._warmed_prefill = 0
        # typed registry series replacing the former hand-rolled counter
        # attributes; the steady-state throughput pairs (tokens + seconds)
        # exclude steps that compiled a fresh jit signature
        reg = self.registry
        self._c_decode_steps = reg.counter(
            "serve_decode_steps_total", "decode steps run")
        self._c_decode_tokens = reg.counter(
            "serve_decode_tokens_total",
            "steady-state decoded tokens (compile steps excluded)")
        self._c_decode_seconds = reg.counter(
            "serve_decode_seconds_total",
            "steady-state decode wall time (compile steps excluded)")
        self._c_prefill_batches = reg.counter(
            "serve_prefill_batches_total", "batched suffix prefill calls")
        self._c_prefill_tokens = reg.counter(
            "serve_prefill_tokens_total",
            "steady-state prefilled suffix tokens (compiles excluded)")
        self._c_prefill_seconds = reg.counter(
            "serve_prefill_seconds_total",
            "steady-state batched-prefill wall time (compiles excluded)")
        self._c_prompt_tokens = reg.counter(
            "serve_prompt_tokens_total", "prompt tokens submitted to prefill")
        self._c_prefix_hit_tokens = reg.counter(
            "serve_prefix_hit_tokens_total",
            "prompt tokens satisfied from the prefix cache")
        self._c_finished = reg.counter(
            "serve_requests_finished_total", "requests run to completion")
        self._c_new_tokens = reg.counter(
            "serve_new_tokens_total", "tokens generated by finished requests")
        if self._spec:
            # registered only in speculative mode: the non-spec registry
            # schema (docs/observability.md, tests/test_obs.py) is frozen
            self._c_spec_rounds = reg.counter(
                "serve_spec_rounds_total", "speculative draft+verify rounds")
            self._c_spec_proposed = reg.counter(
                "serve_spec_proposed_tokens_total",
                "draft tokens proposed to the verifier")
            self._c_spec_accepted = reg.counter(
                "serve_spec_accepted_tokens_total",
                "draft tokens accepted by the target")
        self._h_ttft = reg.histogram(
            "serve_ttft_seconds", LATENCY_BUCKETS,
            "arrival -> first generated token")
        self._h_step = reg.histogram(
            "serve_decode_step_seconds", LATENCY_BUCKETS,
            "steady-state decode step wall time (inter-token latency)")
        # SLO accounting: per-request TPOT / end-to-end latency observed at
        # _finish(), and goodput as a callback gauge over the finished list
        # (reset_metrics() clears the list, so the gauge resets with it)
        self._h_tpot = reg.histogram(
            "serve_tpot_seconds", LATENCY_BUCKETS,
            "per-request mean time per output token after the first")
        self._h_e2e = reg.histogram(
            "serve_request_e2e_seconds", LATENCY_BUCKETS,
            "arrival -> request completion")
        reg.gauge("serve_slo_goodput",
                  "fraction of finished requests meeting the TTFT/TPOT "
                  "SLOs (1.0 with no SLO set or nothing finished)",
                  fn=self._slo_goodput)
        reg.gauge("serve_running_requests", "requests in the decode batch",
                  fn=lambda: len(self.scheduler.running))
        reg.gauge("serve_decode_compiles", "decode jit cache entries",
                  fn=self.decode_compile_count)
        reg.gauge("serve_prefill_compiles", "prefill jit cache entries",
                  fn=self.prefill_compile_count)
        reg.gauge("serve_warmup_seconds", "wall time spent in warmup()",
                  fn=lambda: self._warmup_seconds)
        reg.gauge("serve_post_warmup_compiles",
                  "decode+prefill jit compiles not covered by warmup()",
                  fn=self.post_warmup_compiles)
        # every jitted program is a named def: its name is the XLA module's
        # (``jit_serve_decode_paged``), which the device trace shows
        m, cd = model, compute_dtype

        def serve_prefill(p, tk, c, **kw):
            return m.prefill(p, tk, c, ctx=ctx, compute_dtype=cd, **kw)

        def serve_decode(p, tk, c, pos):
            return m.decode_step(p, tk, c, pos, ctx=ctx, compute_dtype=cd)

        def serve_decode_paged(p, tk, c, pos, bt):
            return m.decode_step(p, tk, c, pos, ctx=ctx, compute_dtype=cd,
                                 block_tables=bt)

        def serve_prefill_chunk(p, tk, c, pos, lens):
            return m.prefill_chunk(p, tk, c, pos, lens, ctx=ctx,
                                   compute_dtype=cd)

        def serve_prefill_paged(p, tk, c, pos, lens, bt):
            return m.prefill_chunk(p, tk, c, pos, lens, ctx=ctx,
                                   compute_dtype=cd, block_tables=bt)

        def serve_sample(lg, t, k):
            # sampled tokens plus, per row, whether every logit was finite
            return _sample_rows(lg, t, k), jnp.all(jnp.isfinite(lg), axis=-1)

        self._prefill = jax.jit(serve_prefill)
        self._decode = jax.jit(serve_decode)
        # page stores are donated so XLA writes the new token in place
        # instead of copying every page each step
        self._decode_paged = jax.jit(serve_decode_paged, donate_argnums=(2,))
        self._prefill_chunk = None
        self._prefill_chunk_paged = None
        if chunk_ok:
            # the gathered suffix-prefill cache is the largest transient in
            # the serving path; donate it so XLA updates it in place instead
            # of holding input + output copies alive
            self._prefill_chunk = jax.jit(serve_prefill_chunk,
                                          donate_argnums=(2,))
        if self.prefill_kernel:
            # page stores donated, like decode: the suffix K/V scatter and
            # the chunked-prefill kernel update the pages in place
            self._prefill_chunk_paged = jax.jit(serve_prefill_paged,
                                                donate_argnums=(2,))
        self._sample = jax.jit(serve_sample)
        if self._spec:
            spec_steps = self.spec_k + 1

            def serve_spec_draft(p, tok, cache, pos, bt, temps, seeds,
                                 offs):
                # ONE dispatch proposes the whole k-token draft run: the
                # scan feeds the last committed token then each proposal
                # back in, sampling in-scan (keys derived in-graph from the
                # request seeds, folded per output index — preemption-safe
                # and decorrelated from the non-spec decode keys). One extra
                # step (spec_steps = k + 1) writes the last proposal's K/V
                # so a fully-accepted round leaves no hole in the draft
                # cache; its sampled token is discarded.
                base = jax.vmap(lambda s: jax.random.fold_in(
                    jax.random.PRNGKey(s), _DRAFT_FOLD))(seeds)

                def body(carry, i):
                    tok_c, pos_c, cache_c = carry
                    logits, cache_c = m.decode_step(
                        p, tok_c, cache_c, pos_c, ctx=ctx, compute_dtype=cd,
                        block_tables=bt)
                    keys = jax.vmap(jax.random.fold_in)(base, offs + i)
                    nxt = _sample_rows(logits, temps, keys)
                    return (nxt[:, None], pos_c + 1, cache_c), (nxt, logits)

                (_, _, cache), (props, logits) = jax.lax.scan(
                    body, (tok, pos, cache), jnp.arange(spec_steps))
                return props, logits, cache

            self._spec_draft = jax.jit(serve_spec_draft,
                                       donate_argnums=(2,))

            def serve_verify(p, tk, c, pos, lens, bt):
                logits, c = m.verify_chunk(p, tk, c, pos, lens, ctx=ctx,
                                           compute_dtype=cd, block_tables=bt)
                # greedy argmax computed in-graph so greedy rounds transfer
                # (B, k+1) ints, not (B, k+1, vocab) logits
                return logits, jnp.argmax(logits, -1).astype(jnp.int32), c

            self._verify = jax.jit(serve_verify, donate_argnums=(2,))
        else:
            self._spec_draft = None
            self._verify = None

    # ------------------------------------------------------------------ API
    def submit(self, prompt_tokens, max_new_tokens: int, *,
               temperature: float = 0.0, seed: int = 0,
               eos_id: Optional[int] = None,
               extras: Optional[Dict] = None,
               stream_callback: Optional[Callable] = None) -> int:
        """Enqueue one request; returns its id. ``prompt_tokens``: (T0,) ints;
        ``extras``: per-request model inputs shaped (1, ...) — whisper frames,
        vlm vision_embeds. ``stream_callback`` receives a ``StreamEvent`` per
        emitted token (on the detokenize worker thread unless
        ``async_detok=False``)."""
        prompt = np.asarray(prompt_tokens, np.int32).reshape(-1)
        vis = 0
        cfg = getattr(self.model, "cfg", None)
        if (extras and "vision_embeds" in extras and cfg is not None
                and getattr(cfg, "family", "") == "vlm"):
            vis = extras["vision_embeds"].shape[1]
        req = Request(req_id=self._next_id, prompt=prompt,
                      max_new_tokens=max_new_tokens, temperature=temperature,
                      seed=seed, eos_id=eos_id, extras=extras, vis_offset=vis,
                      cacheable=self._chunk_ok and not extras and vis == 0,
                      stream_callback=stream_callback)
        if self._spec and not req.cacheable:
            raise ValueError(
                "speculative decoding serves text-only chunked-prefill "
                "requests (no extras / vision prefixes)")
        # speculative verify transiently writes up to spec_k positions past
        # the budget before rollback — the same headroom admission reserves
        need = self.pool.blocks_for(req.cache_budget()
                                    + (self.spec_k if self._spec else 0))
        if need > self.pool.usable_blocks:
            raise ValueError(
                f"request needs {need} blocks ({req.cache_budget()} cache "
                f"positions) but the pool only has {self.pool.usable_blocks} "
                f"({self.pool.num_blocks} x {self.block_size}-token blocks, "
                "one reserved); raise --num-blocks/--block-size")
        self._next_id += 1
        if self._start_time is None:
            self._start_time = req.arrival_time
        self.scheduler.submit(req)
        if self.flight is not None:
            self.flight.record("submit", req_id=req.req_id,
                               prompt_tokens=int(prompt.size),
                               max_new_tokens=int(max_new_tokens))
        return req.req_id

    def has_work(self) -> bool:
        return self.scheduler.has_work()

    def step(self) -> List[Request]:
        """Admit + prefill joiners (same-length-bucket suffixes batched into
        one jitted call), run one decode step over the running batch; returns
        the requests that finished during this step. A raising step dumps
        the postmortem bundle (when a flight recorder is attached) before
        propagating."""
        self._step_idx += 1
        if self.flight is not None:
            self.flight.begin_step(self._step_idx)
        try:
            done = self._step_inner()
        except Exception as e:
            if self.flight is not None:
                self.flight.record("step_exception", error=repr(e))
                self.dump_postmortem("step_exception")
            raise
        self.last_step_time = time.perf_counter()
        return done

    def _step_inner(self) -> List[Request]:
        if self._recalib is not None:
            # between-steps hook: applies staged hot-swaps first, so a swap
            # always lands on a step boundary, never mid-dispatch
            self._recalib.on_step(self)
        done: List[Request] = []
        admitted: List[Request] = []
        groups: Dict[int, list] = {}
        if self.scheduler.waiting:
            # nothing waits at steady state: no admit span on most steps
            with trace.span("serve.admit") as sp:
                admitted = self.scheduler.admit()
                for req in admitted:
                    if req.cacheable:
                        self._alloc_admitted(req, groups)
                    else:
                        self._prefill_request(req)  # extras / hybrid archs
                sp.set(admitted=len(admitted))
        for _, group in sorted(groups.items()):
            self._prefill_batch(group)
        for req in admitted:
            if req.done:
                self._finish(req)
                done.append(req)
        running = list(self.scheduler.running)
        if running:
            done.extend(self._spec_decode_step(running) if self._spec
                        else self._decode_step(running))
        return done

    def _alloc_admitted(self, req: Request, groups: Dict[int, list]) -> None:
        """Allocate an admitted request's pages (and thereby look up its
        cached prefix) once; the suffix length both picks the request's
        prefill batch group and feeds the prefill."""
        toks = req.prefill_tokens()
        cached = self.pool.alloc(req.req_id, len(toks), tokens=toks)
        if self._spec:
            # lockstep pools: the mirrored call sequence keeps the draft
            # registry identical, so hits (and suffix shapes) match
            dcached = self.draft_pool.alloc(req.req_id, len(toks),
                                            tokens=toks)
            assert dcached == cached, "draft pool diverged from target"
        self._c_prompt_tokens.inc(len(toks))
        self._c_prefix_hit_tokens.inc(cached)
        if self.flight is not None and cached:
            self.flight.record("prefix_hit", req_id=req.req_id,
                               cached_tokens=int(cached))
        if self._recalib is not None:
            # capture rides the admission path: the recalibrator replays
            # exactly the tokens this prefill is about to compute over
            self._recalib.on_prefill(self, req)
        groups.setdefault(self._bucket_prefill(len(toks) - cached),
                          []).append((req, toks, cached))

    def fork(self, req_id: int, *, temperature: Optional[float] = None,
             seed: Optional[int] = None) -> int:
        """Clone a running request mid-generation (best-of-n sampling): the
        child shares the parent's cache blocks copy-on-write — the first
        divergent token write into the shared tail block copies just that
        block. Returns the child's request id."""
        parent = next((r for r in self.scheduler.running
                       if r.req_id == req_id), None)
        if parent is None:
            raise ValueError(f"request {req_id} is not running")
        if len(self.scheduler.running) >= self.scheduler.max_running:
            raise ValueError("running set full; cannot fork")
        if seed is None:
            # derive a distinct, deterministic child seed by folding the
            # child's req_id into the parent's: defaulting to parent.seed
            # would replay the parent's exact trajectory at temperature > 0,
            # making best-of-n forks identical. Passing seed explicitly
            # (including parent.seed) keeps the old behavior.
            seed = parent.seed ^ ((0x9E3779B9 * (self._next_id + 1))
                                  & 0x7FFFFFFF)
        child = Request(
            req_id=self._next_id, prompt=parent.prompt.copy(),
            max_new_tokens=parent.max_new_tokens,
            temperature=parent.temperature if temperature is None
            else temperature,
            seed=seed,
            eos_id=parent.eos_id, extras=parent.extras,
            vis_offset=parent.vis_offset, cacheable=parent.cacheable)
        self._next_id += 1
        child.out_tokens = list(parent.out_tokens)
        child.cache_len = parent.cache_len
        # the child continues the parent's lifecycle: keep both timestamps
        # so its TTFT equals the parent's (arrival defaulted to the fork
        # instant, which would make first_token - arrival negative)
        child.arrival_time = parent.arrival_time
        child.first_token_time = parent.first_token_time
        self.pool.fork(parent.req_id, child.req_id)
        if self._spec:
            self.draft_pool.fork(parent.req_id, child.req_id)
        self.scheduler.adopt(child)
        if self.flight is not None:
            self.flight.record("fork", req_id=child.req_id,
                               parent=parent.req_id,
                               at_tokens=len(child.out_tokens))
        return child.req_id

    # ------------------------------------------------------- recalibration
    def attach_recalibrator(self, worker) -> None:
        """Install a live-traffic recalibrator (serve/recalibrate.py's
        ``RecalibWorker``): every ``step()`` calls its ``on_step`` (which
        applies staged hot-swaps and polls the bound gates), admission
        routes sampled prefill streams into its calibrator, and the
        ``serve_recalib_*`` series join the registry. Registered only when
        attached — the base registry schema (docs/observability.md,
        tests/test_obs.py) is frozen, same contract as the spec-only
        series."""
        self._recalib = worker
        worker._engine = self      # reject-path flight/postmortem wiring
        reg = self.registry
        worker.bind_metrics(
            swaps=reg.counter("serve_recalib_swaps_total",
                              "factor hot-swaps applied to the live engine"),
            sampled=reg.counter("serve_recalib_sampled_requests_total",
                                "requests sampled into traffic calibration"),
            tokens=reg.counter("serve_recalib_captured_tokens_total",
                               "served token positions streamed into "
                               "calibration"))
        reg.gauge("serve_recalib_tokens_seen_min",
                  "min calibration tokens streamed over target layers",
                  fn=worker.min_tokens_seen)
        reg.gauge("serve_recalib_bound_clearance",
                  "min tokens_seen / (min_token_factor x n) over target "
                  "layers; the data gate clears at >= 1",
                  fn=worker.clearance)
        reg.gauge("serve_recalib_residual_excess",
                  "worst residual/bound ratio of the last recompression",
                  fn=lambda: worker.last_excess)

    def hot_swap(self, params, draft_params=None) -> None:
        """Swap refreshed factors into the live engine between steps — no
        drain, no retrace. The new pytree must match the live one exactly
        (treedef + per-leaf shape/dtype): params are traced jit *arguments*
        (only caches are donated), so a value-only swap hits every existing
        jit cache entry and ``post_warmup_compiles`` stays 0. In-flight
        requests keep their KV pages; their next decode step simply runs
        the new weights."""
        def _check(name, old, new):
            to, tn = jax.tree.structure(old), jax.tree.structure(new)
            if to != tn:
                raise ValueError(f"hot_swap: {name} treedef mismatch "
                                 f"(rank-unstable recompression?)")
            for lo, ln in zip(jax.tree.leaves(old), jax.tree.leaves(new)):
                so, sn = jnp.shape(lo), jnp.shape(ln)
                do = jnp.result_type(lo)
                dn = jnp.result_type(ln)
                if so != sn or do != dn:
                    raise ValueError(
                        f"hot_swap: {name} leaf changed {so}/{do} -> "
                        f"{sn}/{dn}; swaps must be shape/dtype-stable")
        if draft_params is not None and not self._spec:
            raise ValueError("hot_swap: draft_params given but the engine "
                             "is not in speculative mode")
        _check("params", self.params, params)
        if draft_params is not None:
            _check("draft_params", self.draft_params, draft_params)
        with trace.span("serve.recalib_swap",
                        draft=draft_params is not None):
            self.params = params
            if draft_params is not None:
                self.draft_params = draft_params
        self._swap_epoch += 1
        if self.flight is not None:
            self.flight.record("recalib_swap", epoch=self._swap_epoch,
                               draft=draft_params is not None,
                               in_flight=len(self.scheduler.running))

    def stream(self) -> Iterator[Request]:
        """Drive steps until the queue drains, yielding finished requests.
        With the async pipeline on, a yielded request's detokenized ``text``
        and callbacks may still be in flight — ``flush_stream()`` (which
        ``run()`` calls) waits for them."""
        while self.has_work():
            yield from self.step()

    def flush_stream(self) -> None:
        """Block until every emitted token's detokenize/callback work has
        been delivered by the background worker (no-op when synchronous)."""
        if self._detok is not None:
            self._detok.flush()

    def run(self) -> List[Request]:
        out = list(self.stream())
        self.flush_stream()
        return out

    def run_offline(self, requests, *, sort_by_length: bool = True
                    ) -> List[Request]:
        """MLPerf-style offline batch-inference lane for throughput-bound
        workloads (latency does not matter, tok/s/$ does).

        ``requests``: a sequence of ``(prompt_tokens, max_new_tokens)``
        pairs or dicts of ``submit()`` kwargs. Everything is enqueued up
        front, sorted by prompt length (longest first) so prompts landing
        in the same suffix-length bucket are admitted together and pack
        into shared batched prefill calls; the engine then drives itself to
        drain and flushes the stream pipeline. Returns the finished
        ``Request`` objects in *input* order."""
        norm = []
        for r in requests:
            if isinstance(r, dict):
                norm.append(dict(r))
            else:
                prompt, n = r
                norm.append({"prompt_tokens": prompt, "max_new_tokens": n})
        order = list(range(len(norm)))
        if sort_by_length:
            order.sort(key=lambda i: -len(
                np.asarray(norm[i]["prompt_tokens"]).reshape(-1)))
        with trace.span("serve.run_offline", requests=len(norm)):
            ids = {i: self.submit(**norm[i]) for i in order}
            while self.has_work():
                self.step()
            self.flush_stream()
        by_id = {r.req_id: r for r in self.finished}
        return [by_id[ids[i]] for i in range(len(norm))]

    # -------------------------------------------------------------- warm start
    def warmup_signatures(self, max_len: int):
        """Enumerate every jit signature a trace whose per-request cache
        need stays within ``max_len`` positions can hit.

        Decode: sig ``(b_pad, nb_pad, paged_kernel)`` — every batch bucket
        crossed with every power-of-two block envelope up to the largest a
        ``max_len``-position table can produce (capped by the pool, which a
        real table can never exceed). Chunked prefill: sig ``(b_pad, l_pad,
        nb_pad)`` — for each suffix-length bucket, the shortest suffix that
        maps to it bounds how high a block-aligned cached-prefix offset can
        sit underneath it (``start + suffix <= max_len``), and each
        reachable offset yields one block envelope; without the prefix
        cache the offset is always 0. Returns ``(decode_sigs,
        prefill_sigs)`` as lists of those tuples. In speculative mode the
        decode sigs describe the draft-scan + verify rounds, whose block
        envelope covers the ``spec_k`` transient tail positions a verify
        round writes past the budget."""
        span = max_len + (self.spec_k if self._spec else 0)
        nb_cap = _pow2_at_least(min(self.pool.blocks_for(span),
                                    self.pool.usable_blocks))
        decode = []
        for b in self.bucket_sizes:
            nb = 1
            while nb <= nb_cap:
                decode.append((b, nb, self.paged_kernel))
                nb *= 2
        prefill = []
        if self._chunk_ok:
            l_buckets = sorted({self._bucket_prefill(l)
                                for l in range(1, max_len + 1)})
            prev = 0
            for l_pad in l_buckets:
                len_min = prev + 1          # shortest suffix in this bucket
                prev = l_pad
                if self.prefix_cache:
                    start_max = ((max_len - len_min) // self.block_size
                                 ) * self.block_size
                    starts = range(0, start_max + 1, self.block_size)
                else:
                    starts = (0,)
                nbs = sorted({_pow2_at_least(self.pool.blocks_for(s + l_pad))
                              for s in starts})
                for b in self.bucket_sizes:
                    for nb in nbs:
                        prefill.append((b, l_pad, nb))
        return decode, prefill

    def warmup(self, *, max_len: Optional[int] = None) -> Dict[str, float]:
        """Pre-compile every reachable jit signature against the trash page
        so no admissible request ever waits on XLA: executes (not just
        AOT-lowers — execution is what populates the jit dispatch cache)
        one all-padding call per decode/prefill signature from
        ``warmup_signatures(max_len)``, warms the row sampler at each batch
        bucket and the pool's maintenance jits, and seeds the signature
        sets so the first real step is steady-state for the throughput
        timers. ``max_len`` bounds the worst-case per-request cache
        positions (prompt + generated + vision prefix) to warm for;
        defaults to — and is capped at — pool capacity. Re-running after
        traffic (or with a larger ``max_len``) only compiles what is
        missing. Returns a summary dict; wall time accumulates into
        ``metrics()["warmup_seconds"]``."""
        cap = self.pool.usable_blocks * self.block_size
        max_len = cap if max_len is None else min(max_len, cap)
        t0 = time.perf_counter()
        decode_sigs, prefill_sigs = self.warmup_signatures(max_len)
        with trace.span("serve.warmup", max_len=max_len,
                        decode_sigs=len(decode_sigs),
                        prefill_sigs=len(prefill_sigs)):
            span = max_len + (self.spec_k if self._spec else 0)
            self.pool.warm(self.pool.blocks_for(span))
            if self._spec:
                self.draft_pool.warm(self.draft_pool.blocks_for(span))
            for b, nb, _ in decode_sigs:
                if self._spec:
                    self._warm_spec(b, nb)
                else:
                    self._warm_decode(b, nb)
            for b, l, nb in prefill_sigs:
                self._warm_prefill(b, l, nb)
        self._warmed_decode = self.decode_compile_count()
        self._warmed_prefill = self.prefill_compile_count()
        self.warmed = True                  # /healthz readiness flips here
        dt = time.perf_counter() - t0
        self._warmup_seconds += dt
        return {"warmup_seconds": dt, "max_len": float(max_len),
                "decode_signatures": float(len(decode_sigs)),
                "prefill_signatures": float(len(prefill_sigs))}

    def post_warmup_compiles(self) -> int:
        """Decode+prefill jit compiles beyond what ``warmup()`` covered —
        the zero-stall invariant: 0 after warmup under admissible traffic
        (before any warmup it simply counts all compiles)."""
        return ((self.decode_compile_count() - self._warmed_decode)
                + (self.prefill_compile_count() - self._warmed_prefill))

    def _warm_decode(self, b: int, nb: int) -> None:
        """Execute one decode step at signature ``(b, nb)`` with zero rows:
        all-trash tables/slots, so the in-place page writes land in the
        trash page and no real state is touched."""
        sig = (b, nb, self.paged_kernel)
        if sig in self._decode_shapes:
            return
        self._decode_shapes.add(sig)
        tok = jnp.zeros((b, 1), jnp.int32)
        pos = jnp.zeros((b,), jnp.int32)
        if self.paged_kernel:
            tables = self.pool.padded_tables([], rows=b, blocks=nb)
            cache = self.pool.paged_cache([], rows=b)
            logits, cache = self._decode_paged(self.params, tok, cache, pos,
                                               tables)
            self.pool.absorb_paged([], cache, rows=b)
        else:
            cache = self.pool.gather_batch([], rows=b, blocks=nb)
            logits, cache = self._decode(self.params, tok, cache, pos)
            self.pool.scatter_token([], cache, pos, rows=b, blocks=nb)
        self._warm_sample(jax.block_until_ready(logits), b)

    def _warm_spec(self, b: int, nb: int) -> None:
        """Execute one speculative round — draft scan with the draft params
        against the draft pool, then the verifier with the target params —
        at signature ``(b, nb)`` with zero rows (all-trash tables)."""
        sig = (b, nb, self.paged_kernel)
        if sig in self._spec_shapes:
            return
        self._spec_shapes.add(sig)
        k = self.spec_k
        tok = jnp.zeros((b, 1), jnp.int32)
        pos = jnp.zeros((b,), jnp.int32)
        temps = jnp.zeros((b,), jnp.float32)
        seeds = jnp.zeros((b,), jnp.uint32)
        offs = jnp.zeros((b,), jnp.int32)
        vtok = jnp.zeros((b, k + 1), jnp.int32)
        lens = jnp.full((b,), k + 1, jnp.int32)
        # the draft always runs gathered (see _spec_decode_step); only the
        # verifier's read path follows the paged_kernel knob
        dcache = self.draft_pool.gather_batch([], rows=b, blocks=nb)
        props, _, dcache = self._spec_draft(
            self.draft_params, tok, dcache, pos, None, temps, seeds, offs)
        self.draft_pool.scatter_suffix([], dcache, [], [], rows=b,
                                       blocks=nb)
        if self.paged_kernel:
            tables = self.pool.padded_tables([], rows=b, blocks=nb)
            cache = self.pool.paged_cache([], rows=b)
            _, g, cache = self._verify(self.params, vtok, cache, pos, lens,
                                       tables)
            self.pool.absorb_paged([], cache, rows=b)
        else:
            cache = self.pool.gather_batch([], rows=b, blocks=nb)
            _, g, cache = self._verify(self.params, vtok, cache, pos, lens,
                                       None)
            self.pool.scatter_suffix([], cache, [], [], rows=b, blocks=nb)
        jax.block_until_ready((props, g))

    def _warm_prefill(self, b: int, l: int, nb: int) -> None:
        """Execute one batched suffix prefill at signature ``(b, l, nb)``
        with zero rows (per-row lengths 1, offsets 0, all-trash tables).
        In speculative mode the same signature also runs with the draft
        params against the draft pool — a different params pytree is a
        separate entry in the same jit cache."""
        sig = (b, l, nb)
        if sig not in self._prefill_shapes:
            self._prefill_shapes.add(sig)
            tok = jnp.zeros((b, l), jnp.int32)
            pos = jnp.zeros((b,), jnp.int32)
            ln = jnp.ones((b,), jnp.int32)
            if self.prefill_kernel:
                tables = self.pool.padded_tables([], rows=b, blocks=nb)
                cache = self.pool.paged_cache([], rows=b)
                logits, cache = self._prefill_chunk_paged(
                    self.params, tok, cache, pos, ln, tables)
                self.pool.absorb_paged([], cache, rows=b)
            else:
                cache = self.pool.gather_batch([], rows=b, blocks=nb)
                logits, cache = self._prefill_chunk(self.params, tok, cache,
                                                    pos, ln)
                self.pool.scatter_suffix([], cache, [], [], rows=b, blocks=nb)
            self._warm_sample(jax.block_until_ready(logits), b)
        if self._spec and sig not in self._draft_prefill_shapes:
            self._draft_prefill_shapes.add(sig)
            tok = jnp.zeros((b, l), jnp.int32)
            pos = jnp.zeros((b,), jnp.int32)
            ln = jnp.ones((b,), jnp.int32)
            if self.prefill_kernel:
                dtables = self.draft_pool.padded_tables([], rows=b, blocks=nb)
                dcache = self.draft_pool.paged_cache([], rows=b)
                dlogits, dcache = self._prefill_chunk_paged(
                    self.draft_params, tok, dcache, pos, ln, dtables)
                jax.block_until_ready(dlogits)
                self.draft_pool.absorb_paged([], dcache, rows=b)
            else:
                dcache = self.draft_pool.gather_batch([], rows=b, blocks=nb)
                dlogits, dcache = self._prefill_chunk(self.draft_params, tok,
                                                      dcache, pos, ln)
                jax.block_until_ready(dlogits)
                self.draft_pool.scatter_suffix([], dcache, [], [], rows=b,
                                               blocks=nb)

    def _warm_sample(self, logits, b: int) -> None:
        """Warm the row sampler at batch bucket ``b`` (its jit signature
        depends only on the batch, which the warm call's real logits carry)."""
        temps = jnp.zeros((b,), jnp.float32)
        keys = jnp.stack([jax.random.PRNGKey(0)] * b)
        jax.block_until_ready(self._sample(logits, temps, keys))

    def generate(self, prompt_tokens, max_new_tokens: int, *,
                 extras: Optional[Dict] = None, temperature: float = 0.0,
                 seed: int = 0, **_) -> jnp.ndarray:
        """Fixed-batch convenience wrapper matching ``ServeEngine.generate``:
        submits every row, runs to completion, reassembles (B, T0+new)."""
        b, t0 = prompt_tokens.shape
        prompts = np.asarray(prompt_tokens, np.int32)
        ids = []
        for i in range(b):
            ex = None
            if extras:
                ex = {k: v[i:i + 1] for k, v in extras.items()}
            ids.append(self.submit(prompts[i], max_new_tokens,
                                   temperature=temperature, seed=seed + i,
                                   extras=ex))
        by_id = {r.req_id: r for r in self.run() if r.req_id in set(ids)}
        rows = []
        for i, rid in enumerate(ids):
            out = np.asarray(by_id[rid].out_tokens, np.int32)
            out = np.pad(out, (0, max_new_tokens - len(out)))   # early EOS
            rows.append(np.concatenate([prompts[i], out]))
        return jnp.asarray(np.stack(rows), jnp.int32)

    def decode_compile_count(self) -> int:
        """Entries in the decode jit compile caches (the recompile counter
        that shape bucketing keeps ≤ the number of shape buckets)."""
        try:
            n = int(self._decode._cache_size()
                    + self._decode_paged._cache_size())
            if self._spec_draft is not None:
                n += int(self._spec_draft._cache_size())
            if self._verify is not None:
                n += int(self._verify._cache_size())
            return n
        except AttributeError:   # older jax: fall back to signatures seen
            return len(self._decode_shapes) + len(self._spec_shapes)

    def prefill_compile_count(self) -> int:
        """Entries in the prefill jit caches: length-bucketed suffix batching
        keeps this ≤ the number of (batch, length, blocks) prefill buckets
        instead of one compile per distinct prompt length."""
        try:
            n = int(self._prefill._cache_size())
            if self._prefill_chunk is not None:
                n += int(self._prefill_chunk._cache_size())
            if self._prefill_chunk_paged is not None:
                n += int(self._prefill_chunk_paged._cache_size())
            return n
        except AttributeError:   # older jax: fall back to signatures seen
            return len(self._prefill_shapes)

    def reset_metrics(self) -> None:
        """Zero everything request-level — the finished list (and with it
        the TTFT samples), the preemption/queue-wait series, timers, and
        hit-rate accounting — while keeping jit caches and the prefix
        registry warm, so steady-state benchmark passes can't leak warmup
        samples. One call resets the whole registry: engine, scheduler and
        pool series all live in ``self.registry`` (callback gauges keep
        reading live state)."""
        self.finished = []
        self._start_time = None
        self.registry.reset()
        for k in self.pool.stats:
            self.pool.stats[k] = 0

    def metrics(self) -> Dict[str, float]:
        """Aggregate serving metrics over finished requests — a
        compatibility view over ``self.registry`` (same keys as before the
        registry existed; ``registry.snapshot()`` is the superset)."""
        fin = self.finished
        decode_s = self._c_decode_seconds.value
        prefill_s = self._c_prefill_seconds.value
        decode = {
            "decode_compiles": self.decode_compile_count(),
            "decode_shapes": len(self._decode_shapes),
            "decode_steps": int(self._c_decode_steps.value),
            # steady-state decode throughput: steps that compiled a new
            # (batch, blocks) signature are excluded from the timer; a trace
            # where the timer never accumulated (every step compiled, e.g.
            # a single-step run) reports 0.0 rather than inf
            "decode_tok_per_s": (self._c_decode_tokens.value / decode_s
                                 if decode_s > 0.0 else 0.0),
            "prefill_compiles": self.prefill_compile_count(),
            "prefill_shapes": len(self._prefill_shapes),
            "prefill_batches": int(self._c_prefill_batches.value),
            # steady-state batched suffix-prefill throughput (compiling
            # signatures excluded, 0.0 when nothing ran post-compile), and
            # which read path produced it: 1.0 = chunked-prefill kernel,
            # 0.0 = gather oracle
            "prefill_tok_per_s": (self._c_prefill_tokens.value / prefill_s
                                  if prefill_s > 0.0 else 0.0),
            "prefill_kernel": float(self.prefill_kernel),
            "prefix_hit_rate": (self._c_prefix_hit_tokens.value /
                                max(self._c_prompt_tokens.value, 1)),
            "prefix_hit_tokens": int(self._c_prefix_hit_tokens.value),
            "cached_blocks": self.pool.cached_blocks,
            "cow_copies": int(self.registry.get(
                "pool_cow_copies_total").value),
            "prefix_evictions": int(self.registry.get(
                "pool_prefix_evictions_total").value),
            "queue_depth": len(self.scheduler.waiting),
            "preemptions": int(self.registry.get(
                "serve_preemptions_total").value),
            "warmup_seconds": self._warmup_seconds,
            "post_warmup_compiles": self.post_warmup_compiles(),
            "slo_goodput": self._slo_goodput(),
        }
        if self._spec:
            # speculative-mode-only keys: the non-spec metrics() schema is
            # frozen (tests/test_obs.py golden keys)
            proposed = self._c_spec_proposed.value
            decode.update({
                "spec_k": float(self.spec_k),
                "spec_rounds": int(self._c_spec_rounds.value),
                "spec_proposed_tokens": int(proposed),
                "spec_accepted_tokens": int(self._c_spec_accepted.value),
                "spec_accept_rate": (self._c_spec_accepted.value / proposed
                                     if proposed > 0 else 0.0),
            })
        if self._recalib is not None:
            # recalibration-only keys, same frozen-schema contract as spec
            w = self._recalib
            decode.update({
                "recalib_swaps": int(w.swaps),
                "recalib_sampled_requests": int(w.cal.sampled_requests),
                "recalib_captured_tokens": int(w.cal.captured_tokens),
                "recalib_clearance": float(w.clearance()),
                "recalib_residual_excess": float(w.last_excess),
            })
        if not fin:
            # TTFT is undefined with nothing finished: None, never NaN —
            # json.dumps(..., allow_nan=False) must accept this dict (the
            # /snapshot endpoint and postmortem bundles serialize it)
            return {"requests": 0, "requests_per_sec": 0.0, "new_tokens": 0,
                    "tokens_per_sec": 0.0, "mean_ttft_s": None,
                    "max_ttft_s": None, **decode}
        ttfts = [r.ttft for r in fin if r.ttft is not None]
        new_tokens = sum(len(r.out_tokens) for r in fin)
        elapsed = max(max(r.finish_time for r in fin) - self._start_time,
                      1e-9)
        return {
            "requests": len(fin),
            "requests_per_sec": len(fin) / elapsed,
            "new_tokens": new_tokens,
            "tokens_per_sec": new_tokens / elapsed,
            "mean_ttft_s": float(np.mean(ttfts)) if ttfts else None,
            "max_ttft_s": float(np.max(ttfts)) if ttfts else None,
            **decode,
        }

    # ------------------------------------------------------------ internals
    def _emit_stream(self, req: Request, token: int, done: bool) -> None:
        """Hand one emitted token to the host pipeline: enqueued to the
        background worker (O(1) on the dispatch thread) or delivered inline
        when ``async_detok=False``. Skipped when there is nothing to do —
        no detokenizer and no callback on the request."""
        if self.detokenizer is None and req.stream_callback is None:
            return
        index = len(req.out_tokens) - 1
        if self._detok is not None:
            self._detok.submit(req, token, index, done)
        else:
            deliver(req, token, index, done, self.detokenizer)

    @staticmethod
    def _req_tpot(req: Request) -> Optional[float]:
        """Per-request mean time per output token after the first; None
        until finished or with fewer than two tokens (no interval exists)."""
        if req.first_token_time is None or req.finish_time is None:
            return None
        n = len(req.out_tokens)
        if n < 2:
            return None
        return (req.finish_time - req.first_token_time) / (n - 1)

    def _meets_slo(self, req: Request) -> bool:
        """Did a finished request meet the configured latency SLOs? An
        unset SLO (None) is vacuously met; so is a TPOT SLO on a request
        too short to have one."""
        if self.slo_ttft_s is not None:
            t = req.ttft
            if t is None or t > self.slo_ttft_s:
                return False
        if self.slo_tpot_s is not None:
            tp = self._req_tpot(req)
            if tp is not None and tp > self.slo_tpot_s:
                return False
        return True

    def _slo_goodput(self) -> float:
        """Fraction of finished requests meeting the SLOs (1.0 when nothing
        has finished — goodput degrades from perfect, it doesn't start
        broken)."""
        fin = self.finished
        if not fin:
            return 1.0
        return sum(1 for r in fin if self._meets_slo(r)) / len(fin)

    def dump_postmortem(self, reason: str,
                        path: Optional[str] = None) -> Optional[str]:
        """Write the flight recorder's postmortem bundle (ring tail +
        metrics snapshot + engine config + trace tail); returns the path,
        or None when no recorder is attached. Wired to the failure paths —
        step exceptions, recalib gate rejections — and callable from test
        harnesses (the soak suite dumps on pool-invariant failures)."""
        if self.flight is None:
            return None
        try:
            metrics = self.metrics()
        except Exception:            # never let a broken metric eat the dump
            metrics = {}
        config = {
            "block_size": self.block_size,
            "num_blocks": self.pool.num_blocks,
            "max_running": self.scheduler.max_running,
            "bucket_sizes": list(self.bucket_sizes),
            "prefill_bucket_sizes": list(self.prefill_bucket_sizes),
            "paged_kernel": self.paged_kernel,
            "prefill_kernel": self.prefill_kernel,
            "prefix_cache": self.prefix_cache,
            "spec": self._spec,
            "spec_k": self.spec_k,
            "slo_ttft_s": self.slo_ttft_s,
            "slo_tpot_s": self.slo_tpot_s,
            "compute_dtype": str(self.compute_dtype),
            "cache_dtype": str(self.cache_dtype),
            "step": self._step_idx,
            "swap_epoch": self._swap_epoch,
        }
        return self.flight.dump(reason=reason, metrics=metrics,
                                config=config, path=path)

    def _finish(self, req: Request) -> None:
        self.scheduler.evict(req)
        if self._spec:
            self.draft_pool.free(req.req_id)
        self.finished.append(req)
        self._c_finished.inc()
        self._c_new_tokens.inc(len(req.out_tokens))
        self._h_e2e.observe(req.finish_time - req.arrival_time)
        tpot = self._req_tpot(req)
        if tpot is not None:
            self._h_tpot.observe(tpot)
        if self.flight is not None:
            self.flight.record("finish", req_id=req.req_id,
                               new_tokens=len(req.out_tokens),
                               preemptions=req.preemptions,
                               ttft_s=req.ttft, tpot_s=tpot,
                               slo_ok=self._meets_slo(req))
        if self._recalib is not None:
            # completion capture: the generated inputs (out_tokens[:-1])
            # stream into calibration once the request's tail is known
            self._recalib.on_finish(self, req)

    def _bucket_batch(self, n: int) -> int:
        for b in self.bucket_sizes:
            if b >= n:
                return b
        return n

    def _bucket_prefill(self, n: int) -> int:
        """Suffix-length bucket: explicit sizes if given, else powers of two
        with a floor of 8 (padding a handful of tokens is cheaper than a
        fresh XLA compile per prompt length)."""
        for b in self.prefill_bucket_sizes:
            if b >= n:
                return b
        return max(_pow2_at_least(n), 8)

    def _sample_tokens(self, logits, reqs, pad_to: int = 0) -> np.ndarray:
        """Row-wise sampling; rows past ``len(reqs)`` are bucket padding
        (sampled greedily on garbage logits, discarded by the caller). A
        request whose logits held a NaN or inf is marked
        ``logits_finite = False``."""
        with trace.span("serve.sample", rows=len(reqs)):
            pad = max(pad_to - len(reqs), 0)
            temps = jnp.asarray([r.temperature for r in reqs] + [0.0] * pad,
                                jnp.float32)
            keys = jnp.stack([
                jax.random.fold_in(jax.random.PRNGKey(r.seed),
                                   len(r.out_tokens))
                for r in reqs] + [jax.random.PRNGKey(0)] * pad)
            toks, finite = self._sample(logits, temps, keys)
            for r, ok in zip(reqs, np.asarray(finite)):
                r.logits_finite &= bool(ok)
            return np.asarray(toks)[:len(reqs)]

    def _prefill_request(self, req: Request) -> None:
        with trace.span("serve.prefill_request", req_id=req.req_id,
                        tokens=len(req.prompt)):
            tokens = req.prefill_tokens()
            l0 = req.vis_offset + len(tokens)
            self.pool.alloc(req.req_id, l0)
            nb = len(self.pool.table(req.req_id))
            cache = self.model.init_cache(1, nb * self.block_size,
                                          dtype=self.cache_dtype)
            kw = dict(req.extras or {})
            logits, cache = self._prefill(self.params,
                                          jnp.asarray(tokens)[None],
                                          cache, **kw)
            logits = logits[:, -1] if logits.ndim == 3 else logits
            self.pool.scatter_prefill([req.req_id], cache, l0)
            req.cache_len = l0
            tok = int(self._sample_tokens(logits, [req])[0])
            req.out_tokens.append(tok)
            self._emit_stream(req, tok, req.done)
            if req.first_token_time is None:
                req.first_token_time = time.perf_counter()
                self._h_ttft.observe(req.ttft)
                if self.flight is not None:
                    self.flight.record("first_token", req_id=req.req_id,
                                       ttft_s=req.ttft)

    def _prefill_batch(self, group) -> None:
        """One jitted prefill over a same-bucket group of (request, tokens,
        cached-prefix-len) joiners, already allocated by ``step()``: each row
        prefills only the suffix its cached prefix does not cover, at its own
        cache offset, padded to the (batch, suffix-len, blocks) bucket.

        ``prefill_kernel=True`` (the default where supported) hands the
        pool's page stores straight to the jitted ``prefill_chunk`` with the
        per-request block tables: attention scatters the suffix K/V into its
        pages and attends through the indirection
        (``kernels/chunked_prefill.py``); the donated stores flow back via
        ``absorb_paged`` — no gather/scatter of the cache. The gather path
        stays as the in-tree oracle."""
        with trace.span("serve.prepare"):
            reqs = [r for r, _, _ in group]
            ids = [r.req_id for r in reqs]
            starts = [cached for _, _, cached in group]
            suffixes = [np.asarray(toks[cached:], np.int32)
                        for _, toks, cached in group]
            lens = [len(s) for s in suffixes]
            l_pad = self._bucket_prefill(max(lens))
            b_pad = self._bucket_batch(len(group))
            nb_pad = _pow2_at_least(max(self.pool.blocks_for(s + l_pad)
                                        for s in starts))
            sig = (b_pad, l_pad, nb_pad)
            if self.flight is not None:
                for r, ln_i in zip(reqs, lens):
                    self.flight.record("prefill", req_id=r.req_id,
                                       suffix_tokens=int(ln_i), bucket=l_pad,
                                       batch=len(group))
            fresh = sig not in self._prefill_shapes or (
                self._spec and sig not in self._draft_prefill_shapes)
            self._prefill_shapes.add(sig)
            if self._spec:
                self._draft_prefill_shapes.add(sig)
            if fresh:
                trace.instant("serve.prefill_compile", sig=sig)
            tok = np.zeros((b_pad, l_pad), np.int32)
            for i, s in enumerate(suffixes):
                tok[i, :len(s)] = s
            pos = jnp.asarray(starts + [0] * (b_pad - len(group)), jnp.int32)
            ln = jnp.asarray(lens + [1] * (b_pad - len(group)), jnp.int32)
        t0 = time.perf_counter()
        with trace.span("serve.prefill_batch", rows=len(group),
                        padded_rows=b_pad, tokens=sum(lens),
                        padded_tokens=b_pad * l_pad, sig=sig):
            if self.prefill_kernel:
                tables = self.pool.padded_tables(ids, rows=b_pad,
                                                 blocks=nb_pad)
                cache = self.pool.paged_cache(ids, rows=b_pad)
                logits, cache = self._prefill_chunk_paged(
                    self.params, jnp.asarray(tok), cache, pos, ln, tables)
                logits = jax.block_until_ready(logits)
                self.pool.absorb_paged(ids, cache, rows=b_pad)
            else:
                cache = self.pool.gather_batch(ids, rows=b_pad, blocks=nb_pad)
                logits, cache = self._prefill_chunk(self.params,
                                                    jnp.asarray(tok),
                                                    cache, pos, ln)
                logits = jax.block_until_ready(logits)
                self.pool.scatter_suffix(ids, cache, starts, lens, rows=b_pad,
                                         blocks=nb_pad)
            if self._spec:
                # the draft prefills the same suffixes at the same offsets
                # into its own pool (logits discarded — the first proposal
                # chains off the target's sampled token)
                with trace.span("serve.spec_draft_prefill", batch=len(group)):
                    if self.prefill_kernel:
                        dtables = self.draft_pool.padded_tables(
                            ids, rows=b_pad, blocks=nb_pad)
                        dcache = self.draft_pool.paged_cache(ids, rows=b_pad)
                        dlogits, dcache = self._prefill_chunk_paged(
                            self.draft_params, jnp.asarray(tok), dcache, pos,
                            ln, dtables)
                        jax.block_until_ready(dlogits)
                        self.draft_pool.absorb_paged(ids, dcache, rows=b_pad)
                    else:
                        dcache = self.draft_pool.gather_batch(
                            ids, rows=b_pad, blocks=nb_pad)
                        dlogits, dcache = self._prefill_chunk(
                            self.draft_params, jnp.asarray(tok), dcache, pos,
                            ln)
                        jax.block_until_ready(dlogits)
                        self.draft_pool.scatter_suffix(
                            ids, dcache, starts, lens, rows=b_pad,
                            blocks=nb_pad)
        if not fresh:                       # steady-state timer: skip compiles
            self._c_prefill_seconds.inc(time.perf_counter() - t0)
            self._c_prefill_tokens.inc(sum(lens))
        self._c_prefill_batches.inc()
        nxt = self._sample_tokens(logits, reqs, pad_to=b_pad)
        with trace.span("serve.emit") as sp:
            now = time.perf_counter()
            finished = 0
            for r, start, ln_i, t in zip(reqs, starts, lens, nxt):
                r.cache_len = start + ln_i
                r.out_tokens.append(int(t))
                fin = bool(r.done)
                finished += fin
                self._emit_stream(r, int(t), fin)
                if r.first_token_time is None:
                    r.first_token_time = now
                    self._h_ttft.observe(r.ttft)
                    if self.flight is not None:
                        self.flight.record("first_token", req_id=r.req_id,
                                           ttft_s=r.ttft)
                self.pool.commit(r.req_id, r.prefill_tokens()[:r.cache_len])
                if self._spec:
                    self.draft_pool.commit(r.req_id,
                                           r.prefill_tokens()[:r.cache_len])
            # a request done at its first token is finished by step()
            sp.set(finished=finished)

    def _decode_step(self, running: List[Request]) -> List[Request]:
        with trace.span("serve.prepare"):
            # reserve the next position for everyone, preempting the
            # youngest request when the pool runs dry
            while True:
                try:
                    for r in running:
                        self.pool.extend(r.req_id, r.cache_len + 1)
                    break
                except MemoryError:
                    victim = self.scheduler.preempt_youngest()
                    running = [r for r in running if r is not victim]
                    if not running:
                        raise MemoryError(
                            "block pool too small for a single request")
            ids = [r.req_id for r in running]
            b_real = len(ids)
            # bucket the (batch, blocks) envelope to a closed signature set;
            # padding rows carry pos 0 and all-trash tables/slots
            b_pad = self._bucket_batch(b_real)
            nb_pad = _pow2_at_least(self.pool.max_table_blocks(ids))
            sig = (b_pad, nb_pad, self.paged_kernel)
            fresh = sig not in self._decode_shapes
            self._decode_shapes.add(sig)
            if fresh:
                trace.instant("serve.decode_compile", sig=sig)
            tables = self.pool.padded_tables(ids, rows=b_pad, blocks=nb_pad)
            tok = jnp.asarray([[r.out_tokens[-1]] for r in running]
                              + [[0]] * (b_pad - b_real), jnp.int32)
            pos = jnp.asarray([r.cache_len for r in running]
                              + [0] * (b_pad - b_real), jnp.int32)
        t0 = time.perf_counter()
        with trace.span("serve.decode_step", rows=b_real, padded_rows=b_pad,
                        sig=sig):
            if self.paged_kernel:
                cache = self.pool.paged_cache(ids, rows=b_pad)
                logits, cache = self._decode_paged(self.params, tok, cache,
                                                   pos, tables)
                self.pool.absorb_paged(ids, cache, rows=b_pad)
            else:
                cache = self.pool.gather_batch(ids, rows=b_pad, blocks=nb_pad)
                logits, cache = self._decode(self.params, tok, cache, pos)
                self.pool.scatter_token(ids, cache, pos, rows=b_pad,
                                        blocks=nb_pad)
            logits = jax.block_until_ready(logits)
        self._c_decode_steps.inc()
        if not fresh:                       # steady-state timer: skip compiles
            dt = time.perf_counter() - t0
            self._c_decode_seconds.inc(dt)
            self._c_decode_tokens.inc(b_real)
            self._h_step.observe(dt)
        for r in running:
            r.cache_len += 1
        nxt = self._sample_tokens(logits, running, pad_to=b_pad)
        done = []
        with trace.span("serve.emit") as sp:
            for r, t in zip(running, nxt):
                r.out_tokens.append(int(t))
                self._emit_stream(r, int(t), r.done)
                if (self.prefix_cache and r.cacheable
                        and r.cache_len % self.block_size == 0):
                    # a generated block just filled: register it so
                    # identical traffic (and this request, if preempted)
                    # can reuse it
                    self.pool.commit(r.req_id,
                                     r.prefill_tokens()[:r.cache_len])
                if r.done:
                    self._finish(r)
                    done.append(r)
            sp.set(finished=len(done))
        return done

    def _spec_decode_step(self, running: List[Request]) -> List[Request]:
        """One speculative round over the running set: the draft scan
        proposes ``spec_k`` tokens per request, the target verifies all
        ``spec_k + 1`` positions in one chunked call, accepted tokens (plus
        the target's bonus/resample token) are emitted, and both pools roll
        back to the accepted length (``truncate``).

        Position bookkeeping: a round starts at ``c = cache_len`` with last
        emitted token ``t`` not yet written. The draft writes positions
        ``c .. c+k`` (feeding ``t, d_1 .. d_k``); the verifier writes the
        same span with the same tokens and ``logits[i]`` scores the token
        after position ``c + i``. Appending ``m`` accepted tokens advances
        ``cache_len`` by ``m``, so the last-token-unwritten invariant and
        draft/target lockstep hold for every acceptance count; stale K/V
        past the accepted length sits at positions the next round rewrites
        before any causal mask can read them."""
        k = self.spec_k
        # reserve the full verify span [c, c+k] in both pools, COW-securing
        # every block it covers; preempt the youngest when the pool runs dry
        while True:
            try:
                for r in running:
                    self.pool.extend(r.req_id, r.cache_len + k + 1,
                                     write_start=r.cache_len)
                    self.draft_pool.extend(r.req_id, r.cache_len + k + 1,
                                           write_start=r.cache_len)
                break
            except MemoryError:
                victim = self.scheduler.preempt_youngest()
                if victim is not None:
                    self.draft_pool.free(victim.req_id)
                running = [r for r in running if r is not victim]
                if not running:
                    raise MemoryError(
                        "block pool too small for a single request")
        ids = [r.req_id for r in running]
        b_real = len(ids)
        b_pad = self._bucket_batch(b_real)
        nb_pad = _pow2_at_least(self.pool.max_table_blocks(ids))
        sig = (b_pad, nb_pad, self.paged_kernel)
        fresh = sig not in self._spec_shapes
        self._spec_shapes.add(sig)
        if fresh:
            trace.instant("serve.spec_compile", sig=sig)
        pad = b_pad - b_real
        tok = jnp.asarray([[r.out_tokens[-1]] for r in running]
                          + [[0]] * pad, jnp.int32)
        pos = jnp.asarray([r.cache_len for r in running] + [0] * pad,
                          jnp.int32)
        temps = jnp.asarray([r.temperature for r in running] + [0.0] * pad,
                            jnp.float32)
        seeds = jnp.asarray([r.seed & 0x7FFFFFFF for r in running]
                            + [0] * pad, jnp.uint32)
        offs = jnp.asarray([len(r.out_tokens) for r in running] + [0] * pad,
                           jnp.int32)
        starts = [r.cache_len for r in running]
        t0 = time.perf_counter()
        with trace.span("serve.spec_step", batch=b_real, sig=sig):
            with trace.span("serve.spec_draft", batch=b_real):
                # the draft always runs on the gathered contiguous envelope:
                # one pool read before the scan, one suffix write-back after,
                # so the k+1 in-scan steps touch only the (rows, envelope)
                # scratch instead of round-tripping the full page stores per
                # proposal (backends without buffer donation — CPU — rewrite
                # every page per paged call; amortizing that per round
                # instead of per token is most of the speculative speedup)
                dcache = self.draft_pool.gather_batch(ids, rows=b_pad,
                                                      blocks=nb_pad)
                props, dlogits, dcache = self._spec_draft(
                    self.draft_params, tok, dcache, pos, None, temps,
                    seeds, offs)
                self.draft_pool.scatter_suffix(
                    ids, dcache, starts, [k + 1] * b_real, rows=b_pad,
                    blocks=nb_pad)
                props_h = np.asarray(props)          # (k+1, b_pad)
            vtok = np.zeros((b_pad, k + 1), np.int32)
            for i, r in enumerate(running):
                vtok[i, 0] = r.out_tokens[-1]
                vtok[i, 1:] = props_h[:k, i]
            lens = jnp.full((b_pad,), k + 1, jnp.int32)
            with trace.span("serve.spec_verify", batch=b_real):
                if self.paged_kernel:
                    tables = self.pool.padded_tables(ids, rows=b_pad,
                                                     blocks=nb_pad)
                    cache = self.pool.paged_cache(ids, rows=b_pad)
                    vlogits, greedy, cache = self._verify(
                        self.params, jnp.asarray(vtok), cache, pos, lens,
                        tables)
                    self.pool.absorb_paged(ids, cache, rows=b_pad)
                else:
                    cache = self.pool.gather_batch(ids, rows=b_pad,
                                                   blocks=nb_pad)
                    vlogits, greedy, cache = self._verify(
                        self.params, jnp.asarray(vtok), cache, pos, lens,
                        None)
                    self.pool.scatter_suffix(
                        ids, cache, starts, [k + 1] * b_real, rows=b_pad,
                        blocks=nb_pad)
                g = np.asarray(greedy)               # (b_pad, k+1)
        # full distributions cross the host boundary only when some row
        # actually samples; greedy rounds transfer just proposals + argmax
        if any(r.temperature > 0.0 for r in running):
            vlog = np.asarray(vlogits, np.float32)   # (b_pad, k+1, V)
            dlog = np.asarray(dlogits, np.float32)   # (k+1, b_pad, V)
        emitted = 0
        done: List[Request] = []
        for i, r in enumerate(running):
            d = [int(t) for t in props_h[:k, i]]
            r.spec_proposed += k
            self._c_spec_proposed.inc(k)
            if r.temperature <= 0.0:
                n_acc = 0
                while n_acc < k and d[n_acc] == int(g[i, n_acc]):
                    n_acc += 1
                toks = d[:n_acc] + [int(g[i, n_acc])]
            else:
                toks, n_acc = self._spec_accept_sampled(r, d, vlog[i],
                                                        dlog[:, i])
            r.spec_accepted += n_acc
            self._c_spec_accepted.inc(n_acc)
            if self.flight is not None:
                self.flight.record("spec_round", req_id=r.req_id,
                                   proposed=k, accepted=n_acc)
            keep: List[int] = []
            for t in toks:
                if len(r.out_tokens) + len(keep) >= r.max_new_tokens:
                    break
                keep.append(t)
                if r.eos_id is not None and t == r.eos_id:
                    break
            r.cache_len += len(keep)
            # rollback: both pools drop the uncommitted tail blocks the
            # rejected proposals wrote
            self.pool.truncate(r.req_id, r.cache_len)
            self.draft_pool.truncate(r.req_id, r.cache_len)
            for t in keep:
                r.out_tokens.append(t)
                self._emit_stream(r, t, r.done)
            emitted += len(keep)
            if self.prefix_cache and r.cacheable:
                committed = r.prefill_tokens()[:r.cache_len]
                self.pool.commit(r.req_id, committed)
                self.draft_pool.commit(r.req_id, committed)
            if r.done:
                self._finish(r)
                done.append(r)
        self._c_decode_steps.inc()
        self._c_spec_rounds.inc()
        if not fresh:                       # steady-state timer: skip compiles
            dt = time.perf_counter() - t0
            self._c_decode_seconds.inc(dt)
            self._c_decode_tokens.inc(emitted)
            self._h_step.observe(dt)
        return done

    def _spec_accept_sampled(self, r: Request, d: List[int],
                             vlog_row: np.ndarray, dlog_row: np.ndarray):
        """Standard speculative rejection sampling for one temperature>0 row:
        accept ``d_i`` w.p. ``min(1, p_i(d_i)/q_i(d_i))``; on the first
        rejection draw from the residual ``norm(max(p_i - q_i, 0))``; after
        a full accept draw the bonus token from ``p_{k+1}``. Draws are
        seeded per (request seed, fold tag, output index) so a given round
        is reproducible. ``vlog_row``/``dlog_row``: (k+1, V) target/draft
        logits. Returns (tokens_to_append, n_accepted)."""
        k = self.spec_k
        base = len(r.out_tokens)
        invt = 1.0 / r.temperature
        toks: List[int] = []
        for i in range(k):
            p = _softmax_np(vlog_row[i] * invt)
            q = _softmax_np(dlog_row[i] * invt)
            rng = np.random.default_rng(
                [r.seed & 0x7FFFFFFF, _ACCEPT_FOLD, base + i])
            di = d[i]
            if rng.random() * max(float(q[di]), 1e-30) < float(p[di]):
                toks.append(di)
                continue
            res = np.maximum(p - q, 0.0)
            s = float(res.sum())
            probs = res / s if s > 0.0 else p
            toks.append(int(rng.choice(probs.shape[0], p=probs)))
            return toks, i
        p = _softmax_np(vlog_row[k] * invt)
        rng = np.random.default_rng(
            [r.seed & 0x7FFFFFFF, _BONUS_FOLD, base + k])
        toks.append(int(rng.choice(p.shape[0], p=p)))
        return toks, k
