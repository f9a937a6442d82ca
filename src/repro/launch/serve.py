"""Serving launcher: batched generation with an optionally COALA-compressed
model (the paper's deployment target).

Fixed-batch (legacy fallback):

  PYTHONPATH=src python -m repro.launch.serve --arch smollm_135m --smoke \
      --compress-ratio 0.6 --requests 4 --new-tokens 16

Continuous batching over the paged KV cache — a mixed-length synthetic
request trace (staggered arrivals, varied prompt/output lengths) served for
both the dense and the COALA-compressed model, reporting per-request TTFT
and aggregate requests/sec:

  PYTHONPATH=src python -m repro.launch.serve --smoke --continuous
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.config import CompressConfig
from repro.configs import get_config, get_smoke_config
from repro.core.calibrate import calibrate_model
from repro.core.compress import (compress_model, compress_model_pair,
                                 compression_summary)
from repro.data import DataConfig, TokenPipeline
from repro.models import build_model
from repro.obs import FlightRecorder, TelemetryServer
from repro.obs import trace as obs_trace
from repro.serve import ContinuousEngine, ServeEngine


def synthetic_trace(n_requests: int, vocab_size: int, *, seed: int = 0,
                    min_prompt: int = 4, max_prompt: int = 24,
                    min_new: int = 4, max_new: int = 16,
                    arrival_every: int = 2, shared_prefix: int = 0):
    """Mixed-length request trace with staggered arrivals.

    ``shared_prefix`` prepends one common random token prefix of that length
    to every prompt — the system-prompt-heavy traffic shape prefix caching
    targets. Returns a list of (arrival_step, prompt (T,), max_new_tokens)."""
    rng = np.random.RandomState(seed)
    common = rng.randint(0, vocab_size, (shared_prefix,)).astype(np.int32)
    trace = []
    for i in range(n_requests):
        t0 = int(rng.randint(min_prompt, max_prompt + 1))
        nn = int(rng.randint(min_new, max_new + 1))
        prompt = rng.randint(0, vocab_size, (t0,)).astype(np.int32)
        if shared_prefix:
            prompt = np.concatenate([common, prompt])
        trace.append((i * arrival_every, prompt, nn))
    return trace


def serve_trace(engine: ContinuousEngine, trace, *, temperature: float = 0.0):
    """Replay a trace: submissions are keyed to engine steps, so requests
    join the running decode batch mid-flight."""
    pending = list(trace)
    step = 0
    while pending or engine.has_work():
        while pending and pending[0][0] <= step:
            _, prompt, nn = pending.pop(0)
            engine.submit(prompt, nn, temperature=temperature)
        engine.step()
        step += 1
    return engine.metrics()


def _compressed_params(cfg, model, params, pipe, ratio: float,
                       draft_ratio: float = 0.0):
    """COALA-compress at ``ratio``; with ``draft_ratio`` also build the
    harder-compressed speculative draft from the same calibration pass.
    Returns ``(params, draft_params, reports, draft_reports)`` — the
    reports carry the per-layer ranks live recalibration pins its
    shape-stable rank maps from."""
    cal = calibrate_model(model, params, [pipe.get_batch(i) for i in range(2)])
    ccfg = CompressConfig(method="coala", ratio=ratio, lam=4.0, mu=-1.0)
    if draft_ratio > 0:
        cparams, dparams, reports, dreports = compress_model_pair(
            model, params, cal, ccfg, draft_ratio=draft_ratio)
        print("compression:", compression_summary(reports))
        print("draft compression:", compression_summary(dreports))
        return cparams, dparams, reports, dreports
    cparams, reports = compress_model(model, params, cal, ccfg)
    print("compression:", compression_summary(reports))
    return cparams, None, reports, None


def _serve_dtype(args):
    """Engine compute and cache dtype: the engine's bf16 default, except
    float32 under ``--smoke`` (the CPU preset, where the greedy parity
    checks compare against float32 oracles)."""
    return jnp.float32 if args.smoke else jnp.bfloat16


def _parse_buckets(spec: str):
    """'1,2,4,8' -> (1, 2, 4, 8); empty -> None (engine default)."""
    return tuple(int(s) for s in spec.split(",") if s.strip()) or None


def run_continuous(args, cfg, model, params, pipe):
    if args.requests <= 0:
        print("no requests to serve")
        return None
    # live telemetry plane (docs/observability.md): the HTTP server comes
    # up before compression/warmup so scrapes work for the whole run; one
    # server spans both engines via attach(), and one flight recorder
    # accumulates lifecycle events across them
    server = None
    if args.telemetry_port >= 0:
        server = TelemetryServer(port=args.telemetry_port)
        print(f"telemetry: listening on http://{server.host}:{server.port} "
              "(/metrics /healthz /requests /snapshot)")
    flight = (FlightRecorder(capacity=args.flight_recorder)
              if args.flight_recorder > 0 else None)
    slo_ttft = args.slo_ttft_ms / 1e3 if args.slo_ttft_ms > 0 else None
    slo_tpot = args.slo_tpot_ms / 1e3 if args.slo_tpot_ms > 0 else None
    try:
        return _run_continuous(args, cfg, model, params, pipe,
                               server=server, flight=flight,
                               slo_ttft=slo_ttft, slo_tpot=slo_tpot)
    finally:
        if server is not None:
            server.close()


def _run_continuous(args, cfg, model, params, pipe, *, server, flight,
                    slo_ttft, slo_tpot):
    ratio = args.compress_ratio if args.compress_ratio > 0 else 0.6
    cparams, dparams, reports, dreports = _compressed_params(
        cfg, model, params, pipe, ratio, draft_ratio=args.draft_ratio)
    trace = synthetic_trace(args.requests, cfg.vocab_size, seed=args.seed,
                            max_new=args.new_tokens,
                            shared_prefix=args.shared_prefix)
    tristate = {"auto": None, "on": True, "off": False}
    paged = tristate[args.paged_kernel]
    prefix = tristate[args.prefix_cache]
    prefill = tristate[args.prefill_kernel]
    # warm for exactly the worst per-request cache need this trace can hit
    warm_len = max(len(p) + nn for _, p, nn in trace)
    eng = None
    for name, p in (("dense", params), ("coala", cparams)):
        eng = ContinuousEngine(model, p, compute_dtype=_serve_dtype(args),
                               cache_dtype=_serve_dtype(args),
                               block_size=args.block_size,
                               num_blocks=args.num_blocks,
                               max_running=args.max_running,
                               paged_kernel=paged,
                               prefill_kernel=prefill,
                               bucket_sizes=_parse_buckets(args.bucket_sizes),
                               prefix_cache=prefix,
                               prefill_bucket_sizes=_parse_buckets(
                                   args.prefill_bucket_sizes),
                               async_detok=args.detok_async == "on",
                               draft_params=dparams, spec_k=args.spec_k,
                               slo_ttft_s=slo_ttft, slo_tpot_s=slo_tpot,
                               flight_recorder=flight)
        if server is not None:
            server.attach(eng)
        worker = None
        if args.calibrate_from_traffic and name == "coala":
            # stream this engine's own traffic back into calibration and
            # hot-swap refreshed factors once the error bound clears; the
            # dense engine serves unmodified, as the parity reference
            from repro.core.compress import rank_map_from_reports
            from repro.serve import (RecalibPolicy, RecalibWorker,
                                     TrafficCalibrator)
            policy = RecalibPolicy(
                sample_rate=args.recalib_sample_rate,
                min_token_factor=args.recalib_min_token_factor,
                max_residual_excess=args.recalib_max_residual_excess,
                check_every=args.recalib_check_every)
            tcal = TrafficCalibrator(model, policy=policy, seed=args.seed)
            ccfg = CompressConfig(method="coala", ratio=ratio, lam=4.0,
                                  mu=-1.0)
            worker = RecalibWorker(
                model, params, tcal, ccfg,
                rank_map=rank_map_from_reports(reports),
                draft_ratio=args.draft_ratio,
                draft_rank_map=rank_map_from_reports(dreports)
                if dreports else None,
                async_solve=args.recalib_async == "on")
            eng.attach_recalibrator(worker)
        if args.warmup == "on":
            w = eng.warmup(max_len=warm_len)
            print(f"[{name}] warmup: {w['warmup_seconds']:.2f}s for "
                  f"{int(w['decode_signatures'])} decode + "
                  f"{int(w['prefill_signatures'])} prefill signatures "
                  f"(max_len {int(w['max_len'])})")
        if args.offline:
            reqs = [dict(prompt_tokens=prompt, max_new_tokens=nn,
                         temperature=args.temperature)
                    for _, prompt, nn in trace]
            eng.run_offline(reqs)
            m = eng.metrics()
        else:
            m = serve_trace(eng, trace, temperature=args.temperature)
        path = "paged-kernel" if eng.paged_kernel else "gather"
        mode = "offline" if args.offline else "online"
        print(f"[{name}] per-request TTFT (s):")
        for r in sorted(eng.finished, key=lambda r: r.req_id):
            print(f"  req {r.req_id:3d}: prompt={len(r.prompt):3d} "
                  f"new={len(r.out_tokens):3d} ttft={r.ttft:.3f}s"
                  + (f" (preempted x{r.preemptions})" if r.preemptions else ""))
        print(f"[{name}] aggregate ({path}, {mode}): {m['requests']} requests, "
              f"{m['requests_per_sec']:.2f} req/s, "
              f"{m['tokens_per_sec']:.1f} new tok/s "
              f"({m['decode_tok_per_s']:.1f} decode tok/s steady-state), "
              f"mean TTFT {m['mean_ttft_s']:.3f}s, "
              f"{m['decode_compiles']} decode compiles over "
              f"{m['decode_steps']} steps ({m['decode_shapes']} shape buckets)"
              + (f"; {m['post_warmup_compiles']} post-warmup compiles"
                 if args.warmup == "on" else ""))
        if slo_ttft is not None or slo_tpot is not None:
            print(f"[{name}] SLO goodput {m['slo_goodput']:.2f} "
                  f"(ttft <= {slo_ttft if slo_ttft is not None else '-'}s, "
                  f"tpot <= {slo_tpot if slo_tpot is not None else '-'}s)")
        if "spec_accept_rate" in m:
            print(f"[{name}] speculative (draft ratio {args.draft_ratio}, "
                  f"k={int(m['spec_k'])}): {int(m['spec_rounds'])} rounds, "
                  f"accept rate {m['spec_accept_rate']:.2f} "
                  f"({int(m['spec_accepted_tokens'])}/"
                  f"{int(m['spec_proposed_tokens'])} draft tokens)")
        if worker is not None:
            s = worker.summary()
            print(f"[{name}] recalibration: {s['swaps']} hot-swaps over "
                  f"{s['solve_attempts']} solve attempts, "
                  f"{s['sampled_requests']} sampled requests / "
                  f"{s['captured_tokens']} captured tokens, "
                  f"data clearance {s['clearance']:.2f}, "
                  f"residual excess {s['residual_excess']:.2f}, "
                  f"status {s['status']}; "
                  f"{m['post_warmup_compiles']} post-warmup compiles")
        prefill_path = "chunked-kernel" if eng.prefill_kernel else "gather"
        print(f"[{name}] prefill ({prefill_path}): "
              f"{m['prefill_tok_per_s']:.1f} suffix tok/s steady-state, "
              f"{m['prefill_compiles']} compiles / "
              f"{m['prefill_batches']} batched calls "
              f"({m['prefill_shapes']} length buckets); prefix cache "
              f"{'on' if eng.prefix_cache else 'off'}: "
              f"hit rate {m['prefix_hit_rate']:.2f} "
              f"({m['prefix_hit_tokens']} tokens), "
              f"{m['cached_blocks']} cached blocks, "
              f"{m['cow_copies']} COW copies, "
              f"{m['prefix_evictions']} evictions")
    return eng


def run_fixed(args, cfg, model, params, pipe):
    if args.compress_ratio > 0:
        params, _, _, _ = _compressed_params(cfg, model, params, pipe,
                                             args.compress_ratio)
    eng = ServeEngine(model, params, compute_dtype=_serve_dtype(args),
                      cache_dtype=_serve_dtype(args))
    batch = pipe.get_batch(0)
    extras = {k: v for k, v in batch.items() if k != "tokens"}
    out = eng.generate(batch["tokens"], max_new_tokens=args.new_tokens,
                       extras=extras or None, temperature=args.temperature)
    print(f"served {args.requests} requests x {args.new_tokens} tokens")
    print(out[:, -args.new_tokens:])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_135m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching over the paged KV cache")
    ap.add_argument("--compress-ratio", type=float, default=0.0)
    ap.add_argument("--draft-ratio", type=float, default=0.0,
                    help="self-speculative decoding: also build a harder-"
                         "compressed COALA draft at this kept-parameter "
                         "ratio from the same calibration pass, and serve "
                         "with draft-proposed tokens verified by the target "
                         "(continuous engine only; 0 = off)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens proposed per speculative round "
                         "(used with --draft-ratio)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--block-size", type=int, default=8,
                    help="paged-cache tokens per block")
    ap.add_argument("--num-blocks", type=int, default=256)
    ap.add_argument("--max-running", type=int, default=8)
    ap.add_argument("--paged-kernel", choices=("auto", "on", "off"),
                    default="auto",
                    help="decode read path: paged-attention kernel vs "
                         "gather-into-contiguous (auto: paged where the "
                         "model supports it)")
    ap.add_argument("--prefill-kernel", choices=("auto", "on", "off"),
                    default="auto",
                    help="batched suffix-prefill read path: chunked-prefill "
                         "kernel over the paged pool vs gather-into-"
                         "contiguous (auto: kernel where the model supports "
                         "it)")
    ap.add_argument("--bucket-sizes", default="",
                    help="comma-separated decode batch buckets, e.g. "
                         "'1,2,4,8' (default: powers of two up to "
                         "--max-running)")
    ap.add_argument("--prefix-cache", choices=("auto", "on", "off"),
                    default="auto",
                    help="block-granular prompt-prefix reuse over the paged "
                         "cache (auto: on for pure-attention LMs)")
    ap.add_argument("--prefill-bucket-sizes", default="",
                    help="comma-separated prompt-suffix length buckets for "
                         "batched prefill, e.g. '8,16,32' (default: powers "
                         "of two, floor 8)")
    ap.add_argument("--warmup", choices=("on", "off"), default="off",
                    help="pre-compile every reachable decode/prefill jit "
                         "signature against the trash page before serving, "
                         "so the first request's TTFT equals steady state "
                         "(bounded by the trace's worst-case cache need)")
    ap.add_argument("--offline", action="store_true",
                    help="serve the trace through the offline batch lane "
                         "(run_offline: length-sorted admission, packed "
                         "bucketed prefills) instead of staggered arrivals")
    ap.add_argument("--detok-async", choices=("on", "off"), default="on",
                    help="run detokenize + stream callbacks on the "
                         "background worker thread (off: inline on the "
                         "dispatch thread, the ordering oracle)")
    ap.add_argument("--calibrate-from-traffic", action="store_true",
                    help="stream a sampled fraction of served activations "
                         "into COALA calibration and hot-swap recompressed "
                         "factors into the live engine (no drain) once the "
                         "error bound clears; applies to the coala engine "
                         "of the continuous comparison, and to the draft "
                         "too when --draft-ratio is set")
    ap.add_argument("--recalib-sample-rate", type=float, default=1.0,
                    help="fraction of requests whose token streams feed "
                         "traffic calibration (sticky per request)")
    ap.add_argument("--recalib-min-token-factor", type=float, default=0.25,
                    help="data gate: recompress only once every target "
                         "layer has streamed at least this factor times "
                         "its feature count in calibration tokens (below "
                         "1.0 is safe: the mu-regularized solve covers the "
                         "insufficient-data regime, and the residual-vs-"
                         "bound gate still has to clear)")
    ap.add_argument("--recalib-max-residual-excess", type=float, default=2.0,
                    help="bound gate: ship recompressed factors only if "
                         "every layer's achieved residual is within this "
                         "factor of the attainable error bound")
    ap.add_argument("--recalib-check-every", type=int, default=2,
                    help="poll the recalibration gates every N engine steps")
    ap.add_argument("--recalib-async", choices=("on", "off"), default="off",
                    help="run the recompression solve on a background "
                         "thread that stages the swap for the next step "
                         "boundary (off: solve inline between steps, "
                         "deterministic)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend a common prefix of this many tokens to "
                         "every trace prompt (prefix-cache-heavy traffic)")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome/Perfetto trace_event JSON of the "
                         "serving spans (admission, prefill, decode, "
                         "preemption, COW) to this path")
    ap.add_argument("--metrics-out", default="",
                    help="write the last engine's metrics registry in "
                         "Prometheus text exposition format to this path")
    ap.add_argument("--trace-max-events", type=int, default=0,
                    help="cap the tracer's in-memory event list as a ring "
                         "of the most recent N events, for bounded memory "
                         "on long runs (0 = unbounded)")
    ap.add_argument("--telemetry-port", type=int, default=-1,
                    help="serve live telemetry HTTP endpoints (/metrics, "
                         "/healthz, /requests, /snapshot) from the running "
                         "continuous engines on this port (0 picks an "
                         "ephemeral port; -1 = off)")
    ap.add_argument("--flight-recorder", type=int, default=0,
                    help="record per-request lifecycle events into a ring "
                         "of this capacity and dump a postmortem bundle "
                         "(POSTMORTEM_serve.json) on engine failure paths "
                         "(0 = off)")
    ap.add_argument("--slo-ttft-ms", type=float, default=0.0,
                    help="time-to-first-token SLO in milliseconds; feeds "
                         "the serve_slo_goodput gauge (0 = unset)")
    ap.add_argument("--slo-tpot-ms", type=float, default=0.0,
                    help="per-output-token latency SLO in milliseconds "
                         "(mean after the first token); feeds the "
                         "serve_slo_goodput gauge (0 = unset)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compile_cache.enable()

    if args.trace_out:
        obs_trace.enable(max_events=args.trace_max_events or None)
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pipe = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size,
                                    seq_len=args.prompt_len,
                                    global_batch=args.requests), cfg)
    if args.continuous:
        eng = run_continuous(args, cfg, model, params, pipe)
    else:
        run_fixed(args, cfg, model, params, pipe)
        eng = None
    if args.trace_out:
        n = obs_trace.save(args.trace_out)
        print(f"wrote {n} trace events to {args.trace_out}")
    if args.metrics_out:
        if eng is None:
            print("--metrics-out needs --continuous (registry lives on the "
                  "continuous engine); skipped")
        else:
            with open(args.metrics_out, "w") as f:
                f.write(eng.registry.prometheus())
            print(f"wrote metrics exposition to {args.metrics_out}")


if __name__ == "__main__":
    main()
