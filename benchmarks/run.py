"""Benchmark harness — one function per paper table/figure.

Prints ``name,value,notes`` CSV rows. CPU container: wall times are CPU BLAS
timings (relative ordering is the claim, as in the paper's Table 1/Fig. 3);
TPU-roofline numbers come from the dry-run (§Roofline), not from here.

  PYTHONPATH=src python -m benchmarks.run                  # all
  PYTHONPATH=src python -m benchmarks.run fig1 thm1        # subset
  PYTHONPATH=src python -m benchmarks.run serve --smoke \
      --json BENCH_serve.json                              # CI artifact
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache

# --smoke shrinks the serving trace so the CI bench step stays ~1 min
SMOKE = False
ROWS: list = []


def _t(fn, repeat=3):
    fn()  # warmup/compile
    ts = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return min(ts)


def _row(name, value, notes=""):
    ROWS.append({"name": name, "value": value, "notes": notes})
    print(f"{name},{value},{notes}", flush=True)


def _ill_conditioned_x(n, k, cond=3e7, key=0):
    u = jnp.linalg.qr(jax.random.normal(jax.random.PRNGKey(key), (n, n)))[0]
    v = jnp.linalg.qr(jax.random.normal(jax.random.PRNGKey(key + 1), (k, n)))[0]
    s = jnp.logspace(0, -np.log10(cond), n).astype(jnp.float32)
    return (u * s[None, :]) @ v.T


# ---------------------------------------------------------------------------
# Figure 1: relative error vs rank, Gram-based vs QR-based (fp64 reference)
# ---------------------------------------------------------------------------

def fig1_stability():
    from repro.core import baselines, coala_project
    m, n, k = 96, 128, 256
    w = jax.random.normal(jax.random.PRNGKey(5), (m, n), jnp.float32)
    x = _ill_conditioned_x(n, k)
    w64, x64 = np.asarray(w, np.float64), np.asarray(x, np.float64)
    gram = x @ x.T
    for rank in (8, 16, 32, 64):
        u = np.linalg.svd(w64 @ x64)[0][:, :rank]
        ref = u @ u.T @ w64

        def rel(wa):
            wa = np.asarray(wa, np.float64)
            if not np.all(np.isfinite(wa)):
                return float("inf")
            return float(np.linalg.norm(wa - ref, 2) / np.linalg.norm(ref, 2))

        _row(f"fig1/coala_qr/r{rank}", f"{rel(coala_project(w, x, rank=rank)):.3e}")
        a, b = baselines.svd_llm(w, gram, rank)
        _row(f"fig1/svd_llm_cholesky/r{rank}", f"{rel(a @ b):.3e}",
             "NaN/inf = Cholesky failed (paper Fig.1 behaviour)")
        a, b = baselines.svd_llm_v2(w, gram, rank)
        _row(f"fig1/svd_llm_v2_gram/r{rank}", f"{rel(a @ b):.3e}")


# ---------------------------------------------------------------------------
# Figure 2: activation singular-value spectra (captured from a real forward)
# ---------------------------------------------------------------------------

def fig2_spectrum():
    from repro.configs import get_smoke_config
    from repro.core.calibrate import calibrate_model
    from repro.data import DataConfig, TokenPipeline
    from repro.models import build_model
    cfg = get_smoke_config("llama3_1b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pipe = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size, seq_len=64,
                                    global_batch=4), cfg)
    cal = calibrate_model(model, params, [pipe.get_batch(i) for i in range(2)])
    for path, r in list(cal.r_factors().items())[:4]:
        s = np.linalg.svd(np.asarray(r), compute_uv=False)
        _row(f"fig2/sigma_ratio/{path.split('/')[-1]}",
             f"{s.min() / s.max():.3e}",
             f"sigma_max={s.max():.2e}")


# ---------------------------------------------------------------------------
# Table 1: compression wall time by strategy
# ---------------------------------------------------------------------------

def table1_timing():
    from repro.core import baselines, coala
    m, n, k = 512, 512, 16384
    w = jax.random.normal(jax.random.PRNGKey(0), (m, n), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (n, k), jnp.float32)
    rank = 128

    def run_coala():
        return coala.coala_project(w, x, rank=rank)

    def run_svdllm():
        g = x @ x.T
        a, b = baselines.svd_llm(w, g, rank)
        return a @ b

    def run_v2():
        g = x @ x.T
        a, b = baselines.svd_llm_v2(w, g, rank)
        return a @ b

    def run_coala_rsvd():
        return coala.coala_project(w, x, rank=rank, use_rsvd=True)

    for name, fn in (("coala_qr", run_coala), ("svd_llm", run_svdllm),
                     ("svd_llm_v2", run_v2), ("coala_rsvd", run_coala_rsvd)):
        _row(f"table1/{name}", f"{_t(fn) * 1e6:.0f}", "us_per_call (CPU)")


# ---------------------------------------------------------------------------
# Figure 3: R-factor via QR vs Gram; chunked TSQR vs chunked Gram
# ---------------------------------------------------------------------------

def fig3_qr_vs_gram():
    from repro.core import tsqr
    n = 256
    for k in (1024, 4096, 16384):
        x = jax.random.normal(jax.random.PRNGKey(k), (n, k), jnp.float32)
        qr_t = _t(lambda: tsqr.qr_r(x.T))
        gram_t = _t(lambda: jnp.linalg.cholesky(x @ x.T + 1e-6 * jnp.eye(n)))
        _row(f"fig3/qr_us/k{k}", f"{qr_t * 1e6:.0f}")
        _row(f"fig3/gram_chol_us/k{k}", f"{gram_t * 1e6:.0f}")
    x = jax.random.normal(jax.random.PRNGKey(9), (n, 16384), jnp.float32)
    for chunk in (1024, 4096):
        chunks = [x.T[i:i + chunk] for i in range(0, 16384, chunk)]
        t_tsqr = _t(lambda: tsqr.tsqr_sequential(chunks))
        _row(f"fig3/tsqr_us/chunk{chunk}", f"{t_tsqr * 1e6:.0f}",
             "streaming; never materializes X")


# ---------------------------------------------------------------------------
# Tables 2/3 analogue: compression quality by method on a trained model
# ---------------------------------------------------------------------------

_TRAINED = {}


def _trained_model():
    if _TRAINED:
        return _TRAINED["v"]
    from repro.config import TrainConfig
    from repro.configs import get_smoke_config
    from repro.core.calibrate import calibrate_model
    from repro.data import DataConfig, TokenPipeline
    from repro.models import build_model
    from repro.models.common import CPU_CTX
    from repro.train.train_loop import make_train_state, make_train_step
    cfg = get_smoke_config("llama3_1b")
    model = build_model(cfg)
    pipe = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size, seq_len=64,
                                    global_batch=8, seed=11), cfg)
    tcfg = TrainConfig(lr=3e-3, warmup_steps=5, total_steps=120,
                       schedule="cosine", compute_dtype="float32")
    state = make_train_state(model, tcfg, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(model, tcfg, CPU_CTX))
    for i in range(120):
        state, _ = step(state, pipe.get_batch(i))
    params = state["params"]
    cal = calibrate_model(model, params, [pipe.get_batch(2000 + i)
                                          for i in range(4)])

    def eval_ce(p):
        return float(np.mean([float(model.loss(p, pipe.get_batch(1000 + i),
                                               compute_dtype=jnp.float32)[0])
                              for i in range(4)]))

    _TRAINED["v"] = (cfg, model, params, cal, eval_ce, pipe)
    return _TRAINED["v"]


def table2_compression_quality():
    from repro.config import CompressConfig
    from repro.core.compress import compress_model
    cfg, model, params, cal, eval_ce, _ = _trained_model()
    _row("table2/original_ce", f"{eval_ce(params):.4f}")
    ratio = 0.6
    for method, kw in (("asvd", {}), ("svd_llm", {}), ("svd", {}),
                       ("coala_mu0", dict(method="coala", mu=0.0)),
                       ("coala_mu", dict(method="coala", mu=-1.0, lam=4.0)),
                       ("coala_adaptive", dict(method="coala", mu=0.0,
                                               adaptive_rank=True))):
        ccfg = CompressConfig(method=kw.pop("method", method), ratio=ratio,
                              **kw)
        cp, _ = compress_model(model, params, cal, ccfg)
        _row(f"table2/{method}_ce@{ratio}", f"{eval_ce(cp):.4f}")


def fig5_lambda_sensitivity():
    from repro.config import CompressConfig
    from repro.core.compress import compress_model
    cfg, model, params, cal, eval_ce, _ = _trained_model()
    for lam in (0.5, 1.0, 4.0, 10.0, 40.0):
        cp, _ = compress_model(model, params, cal,
                               CompressConfig(method="coala", ratio=0.6,
                                              lam=lam, mu=-1.0))
        _row(f"fig5/ce@lam{lam}", f"{eval_ce(cp):.4f}",
             "paper: optimal lambda stable in [1;10]")


# ---------------------------------------------------------------------------
# Table 4 analogue: adapter-init methods, few fine-tuning steps
# ---------------------------------------------------------------------------

def table4_adapter_init():
    from repro.config import TrainConfig
    from repro.core.adapters import init_adapters, mask_grads
    from repro.data import DataConfig, TokenPipeline
    from repro.train.optimizer import adamw_init, adamw_update
    cfg, model, params, cal, eval_ce, _ = _trained_model()
    pipe = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size, seq_len=64,
                                    global_batch=8, seed=77), cfg)
    tcfg = TrainConfig(lr=1e-3, warmup_steps=2, total_steps=20,
                       schedule="const", weight_decay=0.0)
    for method in ("lora", "pissa", "corda", "coala_a1", "coala_a2"):
        ap, mask = init_adapters(params, cal.r_factors(), method=method,
                                 rank=8)
        opt = adamw_init(ap)

        @jax.jit
        def step(p, o, batch):
            def lf(p):
                return model.loss(p, batch, compute_dtype=jnp.float32)[0]
            loss, g = jax.value_and_grad(lf)(p)
            g = mask_grads(g, mask)
            p, o, _ = adamw_update(tcfg, p, g, o)
            return p, o, loss

        for i in range(20):
            ap, opt, loss = step(ap, opt, pipe.get_batch(i))
        _row(f"table4/{method}_ce_after_ft", f"{eval_ce(ap):.4f}")


# ---------------------------------------------------------------------------
# Theorem 1: ||W0 - W_mu|| linear in mu + bound
# ---------------------------------------------------------------------------

def thm1_convergence():
    from repro.core import coala_project, theory
    w = jax.random.normal(jax.random.PRNGKey(3), (48, 32), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (32, 12), jnp.float32)  # k<n
    r = 6
    w0 = coala_project(w, x, rank=r)
    errs, mus = [], (1e-2, 1e-3, 1e-4, 1e-5)
    for mu in mus:
        w_mu = coala_project(w, x, rank=r, mu=mu)
        diff = float(jnp.linalg.norm(w0 - w_mu))
        bound = float(theory.thm1_bound(w, x, r, mu))
        errs.append(diff)
        _row(f"thm1/err@mu{mu}", f"{diff:.3e}", f"bound={bound:.3e}")
    slope = np.polyfit(np.log(mus[:3]), np.log(np.maximum(errs[:3], 1e-12)),
                       1)[0]
    _row("thm1/loglog_slope", f"{slope:.2f}", "theory predicts ~1 (linear)")


# ---------------------------------------------------------------------------
# Kernel micro-bench (interpret mode on CPU — correctness path timing only)
# ---------------------------------------------------------------------------

def bench_kernels():
    from repro.kernels import ops, ref
    x = jax.random.normal(jax.random.PRNGKey(0), (512, 512), jnp.float32)
    b_t = jax.random.normal(jax.random.PRNGKey(1), (512, 128), jnp.float32)
    a_t = jax.random.normal(jax.random.PRNGKey(2), (128, 512), jnp.float32)
    _row("kernels/lowrank_linear_us",
         f"{_t(lambda: ops.lowrank_linear(x, b_t, a_t)) * 1e6:.0f}",
         "interpret=True on CPU")
    _row("kernels/lowrank_ref_us",
         f"{_t(lambda: ref.lowrank_linear_ref(x, b_t, a_t)) * 1e6:.0f}")
    a = jax.random.normal(jax.random.PRNGKey(3), (2048, 256), jnp.float32)
    _row("kernels/gram_accum_us", f"{_t(lambda: ops.gram_accum(a)) * 1e6:.0f}")
    q = jax.random.normal(jax.random.PRNGKey(4), (1, 512, 4, 64), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(5), (1, 512, 2, 64), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(6), (1, 512, 2, 64), jnp.float32)
    _row("kernels/flash_attention_us",
         f"{_t(lambda: ops.flash_attention(q, k, v)) * 1e6:.0f}")


# ---------------------------------------------------------------------------
# Serving: continuous batching over the paged KV cache, dense vs compressed
# ---------------------------------------------------------------------------

def _decay_spectrum(params, rate):
    """Impose a geometric singular-value decay on every weight matrix.

    Random-init weights carry a flat singular spectrum, and a low-rank
    draft of a flat-spectrum matrix decorrelates from the target argmax
    almost immediately (near-zero acceptance). Trained LLM weight spectra
    decay fast — the regime COALA targets (PAPER.md §1) — so the
    speculative bench imposes ``sigma_i *= rate**i`` per matrix to
    reproduce that regime without a training run."""
    def walk(node):
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v) for v in node]
        if getattr(node, "ndim", 0) >= 2 and min(node.shape[-2:]) >= 32:
            arr = np.asarray(node, np.float32)
            u, s, vt = np.linalg.svd(arr, full_matrices=False)
            s = s * rate ** np.arange(s.shape[-1])
            return jnp.asarray((u * s[..., None, :]) @ vt, node.dtype)
        return node
    return walk(params)


def bench_serving():
    """Continuous batching on a mixed-length trace: the paged-attention
    kernel read path vs the gather-into-contiguous oracle (dense weights),
    plus dense vs COALA-compressed on the winning path. CPU wall times;
    relative ordering is the claim. Columns per variant: requests/sec,
    aggregate + steady-state decode tokens/sec, mean TTFT, and the decode
    recompile counter (bucketing keeps it ≤ the shape-bucket count). Also:
    prefix-cache on/off TTFT on a shared-prefix trace, chunked-prefill
    kernel vs gather suffix tok/s on a prefill-heavy trace, and
    speculative decoding (COALA self-draft) vs plain decode on a
    decode-heavy trace with decayed-spectrum weights. The JSON row schema
    is documented in docs/benchmarks.md."""
    from repro.config import CompressConfig
    from repro.configs import get_smoke_config
    from repro.core.calibrate import calibrate_model
    from repro.core.compress import compress_model
    from repro.data import DataConfig, TokenPipeline
    from repro.launch.serve import serve_trace, synthetic_trace
    from repro.models import build_model
    from repro.serve import ContinuousEngine
    cfg = get_smoke_config("smollm_135m")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pipe = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                    global_batch=4), cfg)
    cal = calibrate_model(model, params, [pipe.get_batch(i) for i in range(2)])
    ccfg = CompressConfig(method="coala", ratio=0.6, lam=4.0, mu=-1.0)
    cparams, creports = compress_model(model, params, cal, ccfg)
    # skewed mixed lengths: long decodes + short joiners, so the bucketed
    # (B, pow2-blocks) envelope the gather path must materialize each step
    # well exceeds live pool usage — the padding the paged path never copies
    n_req, max_new, num_blocks = (10, 48, 40) if SMOKE else (16, 64, 48)
    trace = synthetic_trace(n_req, cfg.vocab_size, min_prompt=4,
                            max_prompt=24, max_new=max_new, arrival_every=3)

    def run(name, p, paged):
        # best-of-N on the steady-state decode rate (same spirit as _t's
        # min-of-3): single serves are noise-dominated on a shared CPU
        best = None
        for _ in range(2 if SMOKE else 3):
            eng = ContinuousEngine(model, p, compute_dtype=jnp.float32,
                                   cache_dtype=jnp.float32, block_size=8,
                                   num_blocks=num_blocks, max_running=4,
                                   paged_kernel=paged)
            m = serve_trace(eng, trace)
            if best is None or m["decode_tok_per_s"] > best["decode_tok_per_s"]:
                best = m
        m = best
        _row(f"serve/{name}_req_per_s", f"{m['requests_per_sec']:.3f}",
             "incl. compile")
        _row(f"serve/{name}_tok_per_s", f"{m['tokens_per_sec']:.2f}")
        _row(f"serve/{name}_decode_tok_per_s",
             f"{m['decode_tok_per_s']:.2f}", "steady-state (post-compile)")
        _row(f"serve/{name}_mean_ttft_s", f"{m['mean_ttft_s']:.3f}")
        _row(f"serve/{name}_decode_compiles", m["decode_compiles"],
             f"{m['decode_steps']} steps, {m['decode_shapes']} shape buckets")
        return m

    mg = run("gather", params, False)
    mp = run("paged", params, True)
    run("coala_paged", cparams, True)
    _row("serve/paged_vs_gather_decode_speedup",
         f"{mp['decode_tok_per_s'] / max(mg['decode_tok_per_s'], 1e-9):.3f}",
         "acceptance: >= 1.0")

    # prefix caching: system-prompt-heavy traffic (one long shared prefix,
    # short unique tails) served twice per variant — the first pass warms the
    # jit caches (and, with caching on, the block registry), the second is
    # the measured steady state, so the TTFT column compares prefix-hit
    # prefills against cold full-prompt prefills rather than compile noise
    pre_req = 10 if SMOKE else 14
    ptrace = synthetic_trace(pre_req, cfg.vocab_size, min_prompt=2,
                             max_prompt=6, shared_prefix=96, max_new=12,
                             arrival_every=2, seed=7)

    def steady_state(eng, trace, key, better):
        """One warm pass (jit compiles; with caching on, the registry too),
        then best-of-repeats on ``key`` (same spirit as _t's min-of-3: a
        single pass is noise-dominated on a shared CPU)."""
        serve_trace(eng, trace)
        m = None
        for _ in range(2 if SMOKE else 3):
            eng.reset_metrics()
            cur = serve_trace(eng, trace)
            if m is None or better(cur[key], m[key]):
                m = cur
        return m

    def run_prefix(name, on):
        eng = ContinuousEngine(model, params, compute_dtype=jnp.float32,
                               cache_dtype=jnp.float32, block_size=8,
                               num_blocks=160, max_running=4, prefix_cache=on)
        m = steady_state(eng, ptrace, "mean_ttft_s", lambda a, b: a < b)
        _row(f"serve/{name}_mean_ttft_s", f"{m['mean_ttft_s']:.4f}",
             "steady-state (warm jit, best of repeats)")
        _row(f"serve/{name}_cache_hit_rate", f"{m['prefix_hit_rate']:.3f}")
        _row(f"serve/{name}_prefill_compiles", m["prefill_compiles"],
             f"{m['prefill_batches']} batched prefill calls, "
             f"{m['prefill_shapes']} length buckets")
        return m

    mon = run_prefix("prefix_on", True)
    moff = run_prefix("prefix_off", False)
    _row("serve/prefix_cache_hit_rate", f"{mon['prefix_hit_rate']:.3f}",
         "acceptance: > 0")
    _row("serve/prefix_ttft_speedup",
         f"{moff['mean_ttft_s'] / max(mon['mean_ttft_s'], 1e-9):.3f}",
         "prefix-hit vs cold TTFT on the shared-prefix trace; "
         "acceptance: > 1.0")

    # chunked prefill: kernel vs gather on prefill-heavy traffic (long
    # prompts, short outputs). Prefix caching is off so every prompt token
    # rides the batched suffix-prefill path; one warm pass compiles, then
    # the steady-state suffix tok/s of the two read paths are compared.
    fp_req = 6 if SMOKE else 10
    ftrace = synthetic_trace(fp_req, cfg.vocab_size, min_prompt=24,
                             max_prompt=56, max_new=4, arrival_every=2,
                             seed=11)

    def run_prefill(name, kernel_on):
        eng = ContinuousEngine(model, params, compute_dtype=jnp.float32,
                               cache_dtype=jnp.float32, block_size=8,
                               num_blocks=160, max_running=4,
                               prefix_cache=False, prefill_kernel=kernel_on)
        m = steady_state(eng, ftrace, "prefill_tok_per_s",
                         lambda a, b: a > b)
        _row(f"serve/{name}_tok_per_s", f"{m['prefill_tok_per_s']:.1f}",
             "steady-state batched suffix prefill (warm jit, best of "
             "repeats)")
        _row(f"serve/{name}_mean_ttft_s", f"{m['mean_ttft_s']:.4f}")
        _row(f"serve/{name}_compiles", m["prefill_compiles"],
             f"{m['prefill_batches']} batched prefill calls, "
             f"{m['prefill_shapes']} length buckets")
        return m

    mk = run_prefill("prefill_kernel", True)
    mgp = run_prefill("prefill_gather", False)
    _row("serve/prefill_kernel_vs_gather_speedup",
         f"{mk['prefill_tok_per_s'] / max(mgp['prefill_tok_per_s'], 1e-9):.3f}",
         "chunked-prefill kernel vs gather oracle suffix tok/s; "
         "acceptance: >= 1.0")

    # warm start: an AOT-warmed engine vs a cold one on the same trace.
    # The config is deliberately tight (2 batch buckets, one prefill length
    # bucket, no prefix cache) so warmup() compiles a handful of signatures
    # rather than the full production cross-product — the claim is the
    # invariant (first-request TTFT at steady state, zero post-warmup
    # compiles), not warmup wall time. The offline row reuses the warmed
    # engine: the length-sorted batch lane's aggregate new tok/s.
    wtrace = synthetic_trace(6 if SMOKE else 10, cfg.vocab_size, min_prompt=4,
                             max_prompt=14, max_new=8, arrival_every=2,
                             seed=13)
    warm_len = max(len(p) + nn for _, p, nn in wtrace)
    wkw = dict(compute_dtype=jnp.float32, cache_dtype=jnp.float32,
               block_size=8, num_blocks=40, max_running=2,
               bucket_sizes=(1, 2), prefill_bucket_sizes=(32,),
               prefix_cache=False)

    def first_ttft(eng):
        return min(eng.finished, key=lambda r: r.req_id).ttft

    cold = ContinuousEngine(model, params, **wkw)
    serve_trace(cold, wtrace)
    _row("serve/cold_ttft_ms", f"{first_ttft(cold) * 1e3:.1f}",
         "first request on a cold engine (pays jit compiles)")
    warm = ContinuousEngine(model, params, **wkw)
    w = warm.warmup(max_len=warm_len)
    m = serve_trace(warm, wtrace)
    _row("serve/warm_ttft_ms", f"{first_ttft(warm) * 1e3:.1f}",
         "first request after warmup(); acceptance: < cold_ttft_ms")
    _row("serve/warmup_seconds", f"{w['warmup_seconds']:.2f}",
         f"{int(w['decode_signatures'])} decode + "
         f"{int(w['prefill_signatures'])} prefill signatures")
    _row("serve/post_warmup_compiles", m["post_warmup_compiles"],
         "acceptance: == 0 (every signature traffic hit was pre-compiled)")
    warm.reset_metrics()
    off_reqs = [(p, nn) for _, p, nn in wtrace]
    warm.run_offline(off_reqs)
    mo = warm.metrics()
    _row("serve/offline_tok_per_s", f"{mo['tokens_per_sec']:.2f}",
         "run_offline on the warmed engine: length-sorted, packed prefills")

    # observability overhead: the same paged-path trace with the FULL
    # telemetry plane on — span tracing, the live HTTP telemetry server
    # (bound on an ephemeral port, scraped once mid-measurement), a flight
    # recorder, and SLO accounting — vs everything off (the metrics
    # registry is always on; counters are plain attribute adds). Both
    # sides are steady-state best-of-repeats, like every serve row.
    import urllib.request

    from repro.obs import FlightRecorder, TelemetryServer
    from repro.obs import trace as obs_trace

    def mk_obs_engine(full_plane):
        flight = FlightRecorder(capacity=4096) if full_plane else None
        return ContinuousEngine(model, params, compute_dtype=jnp.float32,
                                cache_dtype=jnp.float32, block_size=8,
                                num_blocks=num_blocks, max_running=4,
                                paged_kernel=True,
                                slo_ttft_s=60.0 if full_plane else None,
                                slo_tpot_s=60.0 if full_plane else None,
                                flight_recorder=flight)

    # the two arms run INTERLEAVED and the overhead is the MEDIAN of the
    # per-round on/off ratios: a sequential A/B on a shared CPU measures
    # machine drift, not plane cost, and best-of-N still hands the win to
    # whichever arm drew the luckiest scheduling window (single-pass
    # deltas swing past the 5% bar in either direction)
    eng_off = mk_obs_engine(False)
    eng_on = mk_obs_engine(True)
    server = TelemetryServer(port=0)
    server.attach(eng_on)
    off = on = 0.0
    m_on = None
    ratios = []
    try:
        obs_trace.disable()
        serve_trace(eng_off, trace)                    # warm both jit sets
        obs_trace.enable()
        serve_trace(eng_on, trace)
        for _ in range(5 if SMOKE else 7):
            obs_trace.disable()
            eng_off.reset_metrics()
            r_off = serve_trace(eng_off, trace)["decode_tok_per_s"]
            off = max(off, r_off)
            obs_trace.enable()
            eng_on.reset_metrics()
            cur = serve_trace(eng_on, trace)
            if cur["decode_tok_per_s"] > on:
                on, m_on = cur["decode_tok_per_s"], cur
            ratios.append(cur["decode_tok_per_s"] / max(r_off, 1e-9))
        # prove the plane is actually live while we measure it
        with urllib.request.urlopen(server.url("/healthz"),
                                    timeout=10) as r:
            assert r.getcode() == 200, "/healthz not ready"
    finally:
        obs_trace.disable()
        server.close()
    assert len(eng_on.flight) > 0, "flight recorder saw no events"
    overhead_pct = (1.0 - float(np.median(ratios))) * 100.0
    _row("serve/obs_off_decode_tok_per_s", f"{off:.2f}",
         "telemetry plane fully off (no-op tracer singleton)")
    _row("serve/obs_on_decode_tok_per_s", f"{on:.2f}",
         "tracing + metrics + HTTP server + flight recorder + SLOs")
    _row("serve/obs_overhead_pct", f"{overhead_pct:.2f}",
         "acceptance: < 5 with the full telemetry plane enabled "
         "(median of per-round interleaved on/off throughput ratios)")
    _row("serve/slo_goodput", f"{m_on['slo_goodput']:.3f}",
         "fraction of finished requests inside generous 60s SLOs; "
         "acceptance: == 1.0 on uncontended smoke traffic")
    # latency-distribution rows straight from the registry snapshot — the
    # golden-key schema test (tests/test_obs.py) freezes these names
    snap = eng_on.registry.snapshot()
    for key in ("serve_ttft_seconds_p50", "serve_ttft_seconds_p99",
                "serve_queue_wait_seconds_p50",
                "serve_queue_wait_seconds_p99",
                "serve_decode_step_seconds_p50",
                "serve_decode_step_seconds_p99",
                "serve_tpot_seconds_p50", "serve_tpot_seconds_p99",
                "serve_request_e2e_seconds_p50",
                "serve_request_e2e_seconds_p99"):
        _row(f"serve/{key}", f"{snap[key]:.5f}", "registry snapshot")

    # speculative decoding: target + COALA self-draft built from the same
    # calibration pass (compress_model_pair), served from one engine. Two
    # things make this section's config deliberately different from the
    # rows above:
    #   * the model is scaled up (d_model 512, 4 layers) and the page pool
    #     over-provisioned (256 blocks, as a capacity-sized pool would be):
    #     at smoke dims every matmul is latency-bound and a draft step
    #     costs as much as a target step, so speculation has nothing to
    #     win. The regime it targets — and the one real serving sits in —
    #     is decode dominated by per-step cache/pool traffic, which the
    #     draft's gathered scan amortizes across k+1 proposals per round.
    #   * the served weights get the trained-LLM spectral decay
    #     (_decay_spectrum) first — on flat random-init weights any
    #     compressed draft decorrelates from the target argmax and
    #     acceptance is ~0.
    # Base and spec passes are interleaved (best-of-N each) so slow drift
    # on the shared CPU hits both sides equally.
    import dataclasses
    from repro.core.compress import compress_model_pair
    scfg = dataclasses.replace(cfg, d_model=512, n_heads=8, n_kv_heads=4,
                               d_ff=1536, n_layers=4)
    smodel = build_model(scfg)
    sparams = _decay_spectrum(smodel.init(jax.random.PRNGKey(0)), 0.9)
    spipe = TokenPipeline(DataConfig(vocab_size=scfg.vocab_size, seq_len=32,
                                     global_batch=4), scfg)
    scal = calibrate_model(smodel, sparams,
                           [spipe.get_batch(i) for i in range(2)])
    _, dparams, _, _ = compress_model_pair(
        smodel, sparams, scal,
        CompressConfig(method="coala", ratio=0.6, lam=4.0, mu=-1.0),
        draft_ratio=0.3)
    s_req, s_new = (8, 32) if SMOKE else (10, 40)
    strace = synthetic_trace(s_req, scfg.vocab_size, min_prompt=4,
                             max_prompt=16, min_new=s_new, max_new=s_new,
                             arrival_every=2, seed=17)
    warm_len = max(len(p) + nn for _, p, nn in strace)
    skw = dict(compute_dtype=jnp.float32, cache_dtype=jnp.float32,
               block_size=8, num_blocks=256, max_running=4,
               bucket_sizes=(4,), prefill_bucket_sizes=(16,),
               prefix_cache=False)

    base = ContinuousEngine(smodel, sparams, **skw)
    serve_trace(base, strace)                     # pass 1: compiles + parity
    spec = ContinuousEngine(smodel, sparams, draft_params=dparams, spec_k=4,
                            **skw)
    spec.warmup(max_len=warm_len)
    ms0 = serve_trace(spec, strace)               # pass 1: post-warmup count
    mb = ms = None
    for _ in range(4):
        base.reset_metrics()
        cur = serve_trace(base, strace)
        if mb is None or cur["decode_tok_per_s"] > mb["decode_tok_per_s"]:
            mb = cur
        spec.reset_metrics()
        cur = serve_trace(spec, strace)
        if ms is None or cur["decode_tok_per_s"] > ms["decode_tok_per_s"]:
            ms = cur

    def pass1_tokens(eng):
        fin = sorted(eng.finished, key=lambda r: r.req_id)[:len(strace)]
        return [list(r.out_tokens) for r in fin]

    parity = float(pass1_tokens(spec) == pass1_tokens(base))
    _row("serve/spec_baseline_tok_per_s", f"{mb['decode_tok_per_s']:.2f}",
         "non-speculative decode on the same decayed-spectrum target")
    _row("serve/spec_tok_per_s", f"{ms['decode_tok_per_s']:.2f}",
         "speculative emitted tok/s (COALA draft ratio 0.3, k=4)")
    _row("serve/spec_accept_rate", f"{ms['spec_accept_rate']:.3f}",
         "accepted / proposed draft tokens; acceptance: > 0")
    _row("serve/spec_decode_speedup",
         f"{ms['decode_tok_per_s'] / max(mb['decode_tok_per_s'], 1e-9):.3f}",
         "speculative vs plain decode tok/s, same trace; acceptance: >= 1.0")
    _row("serve/spec_greedy_parity", f"{parity:.1f}",
         "spec output token-exact vs non-spec at temperature 0; "
         "acceptance: == 1.0")
    _row("serve/spec_post_warmup_compiles", ms0["post_warmup_compiles"],
         "draft scan + verify join the warmed jit set; acceptance: == 0")

    # live-traffic recalibration: a sampled fraction of served activations
    # streams back into COALA calibration and, once the data/cond/bound
    # gates clear, rank-pinned recompressed factors hot-swap into the live
    # engine between steps. Rows:
    #   * greedy parity — an engine hot-swapping bitwise-identical factors
    #     every step emits exactly the tokens a never-swapped engine does
    #     (the value-swap no-op; in-flight requests keep their KV pages);
    #   * swaps / post_warmup_compiles — the real recalibration serve
    #     performs >= 1 bound-cleared swap with zero retraces after warmup
    #     (rank-stable shapes hit the live jit cache);
    #   * r_gram_rel_err — traffic-captured R equals an offline Calibrator
    #     fed the same sampled streams, as RᵀR (causal-replay parity).
    from repro.core.calibrate import Calibrator
    from repro.core.compress import rank_map_from_reports
    from repro.serve import RecalibPolicy, RecalibWorker, TrafficCalibrator
    rtrace = synthetic_trace(6, cfg.vocab_size, min_prompt=8, max_prompt=20,
                             max_new=16, arrival_every=2, seed=3)
    rkw = dict(compute_dtype=jnp.float32, cache_dtype=jnp.float32,
               block_size=8, num_blocks=64, max_running=4,
               bucket_sizes=(4,), prefix_cache=False)

    plain = ContinuousEngine(model, cparams, **rkw)
    serve_trace(plain, rtrace)
    ident = ContinuousEngine(model, cparams, **rkw)
    pending = list(rtrace)
    step = 0
    while pending or ident.has_work():
        while pending and pending[0][0] <= step:
            _, prompt, nn = pending.pop(0)
            ident.submit(prompt, nn)
        ident.step()
        if ident.scheduler.running:        # swap while requests in flight
            ident.hot_swap(jax.tree.map(jnp.copy, ident.params))
        step += 1
    ident.flush_stream()

    def out_tokens(eng):
        return [list(r.out_tokens)
                for r in sorted(eng.finished, key=lambda r: r.req_id)]

    _row("serve/recalib_greedy_parity",
         f"{float(out_tokens(ident) == out_tokens(plain)):.1f}",
         "per-step identity hot-swaps leave the token stream bit-exact; "
         "acceptance: == 1.0")

    reng = ContinuousEngine(model, cparams, **rkw)
    reng.warmup(max_len=max(len(p) + nn for _, p, nn in rtrace))
    tcal = TrafficCalibrator(
        model, policy=RecalibPolicy(check_every=1, min_new_tokens=16))
    worker = RecalibWorker(model, params, tcal, ccfg,
                           rank_map=rank_map_from_reports(creports))
    reng.attach_recalibrator(worker)
    mr = serve_trace(reng, rtrace)
    _row("serve/recalib_swaps", worker.swaps,
         f"bound-cleared hot-swaps over {worker.solve_attempts} solve "
         f"attempts ({tcal.captured_tokens} captured tokens); "
         "acceptance: >= 1")
    _row("serve/recalib_post_warmup_compiles", mr["post_warmup_compiles"],
         "rank-pinned factor swaps hit the warmed jit set; acceptance: == 0")
    _row("serve/recalib_swap_ms", f"{worker.last_swap_seconds * 1e3:.3f}",
         "wall time of the last hot_swap (validate + assign, no drain)")
    _row("serve/recalib_tokens_to_clearance", worker.tokens_at_first_swap,
         "captured tokens streamed before the first bound-cleared swap")

    offline = Calibrator()
    for stream in tcal.captured_streams:
        model.capture_forward(params, {"tokens": jnp.asarray(stream)[None]},
                              offline)
    rf_t, rf_o = tcal.r_factors(), offline.r_factors()
    gram_rel = max(
        float(jnp.linalg.norm(rf_t[p].T @ rf_t[p] - rf_o[p].T @ rf_o[p])
              / jnp.linalg.norm(rf_o[p].T @ rf_o[p]))
        for p in rf_o)
    _row("serve/recalib_r_gram_rel_err", f"{gram_rel:.2e}",
         "traffic R vs offline replay of the same streams, as R^T R; "
         "acceptance: < 1e-3")


# ---------------------------------------------------------------------------
# Distributed calibration: sharded vs single-device throughput + parity
# ---------------------------------------------------------------------------

def bench_dist():
    """Sharded (butterfly-TSQR) vs single-device COALA calibration.

    Runs in this process, over a ``data`` mesh of the devices it already
    holds (a second process could not reach an accelerator this one has
    claimed): up to 8 shards, a power of two. On CPU give the process
    fake devices before it starts
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8``); there one
    host's cores compute every shard, so the sharded wall time is an
    upper bound. The parity row is the claim that matters: the distributed
    reduction changes the numbers by fp32 roundoff only. Row schema in
    docs/benchmarks.md.
    """
    from repro.configs import get_smoke_config
    from repro.core.calibrate import calibrate_model
    from repro.data import DataConfig, TokenPipeline
    from repro.dist.calibrate import calibrate_sharded
    from repro.models import build_model
    n = 1
    while n * 2 <= min(len(jax.devices()), 8):
        n *= 2
    if n < 2:
        raise RuntimeError(
            "dist benchmark needs >= 2 devices (on CPU: XLA_FLAGS="
            "--xla_force_host_platform_device_count=8)")
    n_batches = 2 if SMOKE else 4
    cfg = get_smoke_config("smollm_135m")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pipe = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size, seq_len=64,
                                    global_batch=8, seed=5), cfg)
    batches = [pipe.get_batch(i) for i in range(n_batches)]
    tokens = sum(int(b["tokens"].size) for b in batches)

    def timed(fn):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out.r_factors())
        return out, time.perf_counter() - t0

    single, t_single = timed(lambda: calibrate_model(model, params, batches))
    mesh = jax.make_mesh((n,), ("data",), devices=jax.devices()[:n])
    sharded, t_sharded = timed(
        lambda: calibrate_sharded(model, params, batches, mesh))
    rs, rd = single.r_factors(), sharded.r_factors()
    gram_rel = max(
        float(np.linalg.norm(np.asarray(rd[p]).T @ np.asarray(rd[p])
                             - np.asarray(rs[p]).T @ np.asarray(rs[p]))
              / np.linalg.norm(np.asarray(rs[p]).T @ np.asarray(rs[p])))
        for p in rs)
    _row("dist/calib_layers", len(rs), "captured linear layers")
    _row("dist/calib_single_tok_per_s", f"{tokens / t_single:.1f}",
         "single-device Calibrator (streaming TSQR)")
    _row("dist/calib_shards", n, "data shards of the sharded run")
    _row("dist/calib_sharded_tok_per_s", f"{tokens / t_sharded:.1f}",
         f"{n} data shards + butterfly reduce "
         f"({jax.devices()[0].platform})")
    _row("dist/sharded_vs_single_ratio",
         f"{t_single / t_sharded:.3f}",
         "wall-time ratio; >1 means sharded faster (expect ~1/shards on "
         "CPU, ~shards on a real mesh)")
    _row("dist/r_gram_rel_err", f"{gram_rel:.2e}",
         "max over layers of ||R_d^T R_d - R_s^T R_s||/||R_s^T R_s||; "
         "acceptance: < 1e-3")
    if not gram_rel < 1e-3:                   # enforced, not just printed
        raise RuntimeError(
            f"sharded-vs-single R parity regressed: gram_rel "
            f"{gram_rel:.2e} >= 1e-3")


# ---------------------------------------------------------------------------
# Roofline summary from the dry-run artifacts
# ---------------------------------------------------------------------------

def roofline_summary():
    import os
    from repro.roofline.report import load_results
    if not os.path.isdir("experiments/dryrun"):
        _row("roofline/skipped", "no experiments/dryrun directory")
        return
    res = [r for r in load_results() if r.get("status") == "ok"
           and r.get("mesh") == "single"]
    for r in res:
        tag = f"[{r['tag']}]" if r.get("tag") else ""
        _row(f"roofline/{r['arch']}/{r['shape']}{tag}",
             f"{r['roofline_fraction']:.4f}",
             f"dominant={r['dominant']}")


ALL = {
    "fig1": fig1_stability,
    "fig2": fig2_spectrum,
    "table1": table1_timing,
    "fig3": fig3_qr_vs_gram,
    "table2": table2_compression_quality,
    "fig5": fig5_lambda_sensitivity,
    "table4": table4_adapter_init,
    "thm1": thm1_convergence,
    "kernels": bench_kernels,
    "serve": bench_serving,
    "dist": bench_dist,
    "roofline": roofline_summary,
}


def main() -> None:
    global SMOKE
    ap = argparse.ArgumentParser()
    ap.add_argument("names", nargs="*",
                    help=f"benchmarks to run (default: all of {list(ALL)})")
    ap.add_argument("--smoke", action="store_true",
                    help="shrink workloads for the CI smoke step")
    ap.add_argument("--json", metavar="PATH", default="",
                    help="also write rows as JSON (CI uploads BENCH_*.json "
                         "as a per-PR artifact)")
    args = ap.parse_args()
    SMOKE = args.smoke
    compile_cache.enable()
    names = args.names or list(ALL)
    unknown = [n for n in names if n not in ALL]
    if unknown:
        ap.error(f"unknown benchmarks {unknown}; choose from {list(ALL)}")
    print("name,value,notes")
    # a suite that raises or emits zero rows fails the run (after every
    # requested suite has had its turn) — a hollow BENCH_*.json artifact
    # must never reach the perf gate looking like a green result
    errors: dict = {}
    for n in names:
        before = len(ROWS)
        try:
            ALL[n]()
        except Exception as e:                          # noqa: BLE001
            errors[n] = f"{type(e).__name__}: {e}"
            print(f"# ERROR {n}: {errors[n]}", flush=True)
        else:
            if len(ROWS) == before:
                errors[n] = "emitted no rows"
                print(f"# ERROR {n}: emitted no rows", flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"benchmarks": names, "smoke": SMOKE, "rows": ROWS,
                       "errors": errors}, f, indent=1)
        print(f"# wrote {args.json} ({len(ROWS)} rows)", flush=True)
    if errors:
        raise SystemExit(f"benchmark suites failed: {sorted(errors)}")


if __name__ == "__main__":
    main()
