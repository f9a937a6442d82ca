"""Bring-up check of the main path on a TPU, at SmolLM-135M's published widths.

  python chip_smoke.py               # one chip: phases (a)-(e)
  python chip_smoke.py --four-chips  # four chips: sharded calibration only

One process drives every phase, so it alone holds the chip. Phases:

  (a) device check: stop unless JAX's first device is a TPU (JAX falls
      back to the CPU when the TPU fails to start);
  (b) the paged-attention and chunked-prefill kernels, run natively in
      bf16 at 16-token pages, against their ``jax.nn`` references;
  (c) the paper's stability claim at a real layer width: COALA vs the
      Gram path (SVD-LLM v2) against a float64 host truth on a cond-1e9
      calibration matrix, with and without the ``"highest"`` matmul
      precision that calibration and compression run under;
  (d) the pipeline: streaming-QR calibration on seeded ``TokenPipeline``
      batches, COALA compression at ratio 0.6, and the dense and the
      compressed model served through ``ContinuousEngine`` on the paged
      kernels after warmup;
  (e) the last line of standard output is one JSON object naming the
      device.

``--four-chips`` runs only sharded calibration over a ``data=4`` mesh at
full width against single-device calibration on the same batches.
Weights and data are random, made from ``--seed``. Any failed check exits
nonzero before the last line is printed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

ARCH = "smollm_135m"
BLOCK_SIZE = 16
NEW_TOKENS = 64
MAX_RUNNING = 8
CALIB_BATCHES, CALIB_ROWS, CALIB_SEQ = 4, 8, 512      # 16384 tokens
# kernel vs reference, both fed the same bf16 Q/K/V, the reference in fp32
# at "highest" (exact up to fp32 roundoff). bf16 keeps 8 significant bits,
# so one rounding moves a value by at most u = 2^-8 of it. The kernel
# rounds its softmax weights p to bf16 before the PV product (at most
# u * sum(p |v|) / l = u * w, with w the reference run on |V|) and its
# output once (u * |out|); the reference rounds its output once. So
# |kernel - ref| <= 2u * |ref| + u * w to first order, checked per element.
BF16_U = 2.0 ** -8
COALA_MAX_ERR = 1e-2          # paper claim: QR path keeps fp32 accuracy ...
GRAM_MIN_MARGIN = 10.0        # ... and the Gram path is >= 10x worse
PARITY_MAX = 1e-3             # sharded vs single-device R, as R^T R


class Phases:
    """Wall and compile seconds of each phase, from JAX's own events."""

    def __init__(self, jax):
        self.compile_s = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def run(self, name, fn, *args):
        c0, h0, t0 = self.compile_s, self.cache_hits, time.perf_counter()
        out = fn(*args)
        print(f"# phase {name}: {time.perf_counter() - t0:.1f} s wall, "
              f"{self.compile_s - c0:.1f} s compiling, "
              f"{self.cache_hits - h0} persistent-cache hits", flush=True)
        return out


def check(cond, msg):
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")
    print(f"  ok: {msg}", flush=True)


# ---------------------------------------------------------------------------
# (b) kernels at real widths
# ---------------------------------------------------------------------------

def phase_kernels(cfg, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    from repro.kernels.chunked_prefill import chunked_prefill_ref
    from repro.kernels.paged_attention import paged_attention_ref

    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    bs, num_blocks = BLOCK_SIZE, 1024
    rng = np.random.RandomState(seed)
    key = jax.random.PRNGKey(seed)
    kk, kv, kq, kp = jax.random.split(key, 4)
    pages = (num_blocks, hkv, bs, hd)
    k_pages = jax.random.normal(kk, pages, jnp.float32).astype(jnp.bfloat16)
    v_pages = jax.random.normal(kv, pages, jnp.float32).astype(jnp.bfloat16)

    def tables_for(totals, nb):
        ids = rng.permutation(np.arange(1, num_blocks))
        tab = np.zeros((len(totals), nb), np.int32)
        nxt = 0
        for i, t in enumerate(totals):
            n = -(-t // bs)
            tab[i, :n] = ids[nxt:nxt + n]
            nxt += n
        return jnp.asarray(tab)

    def gap(got, ref_fn, args, valid=True):
        """Worst |kernel - ref| over its bound, and the worst error."""
        kpg, vpg, rest = args[1], args[2], args[3:]
        with jax.default_matmul_precision("highest"):
            want = ref_fn(args[0], kpg, vpg, *rest)
            w = ref_fn(args[0], kpg, jnp.abs(vpg), *rest)
        got, want, w = (np.asarray(a, np.float32) for a in (got, want, w))
        err = np.abs(got - want)
        bound = 2 * BF16_U * np.abs(want) + BF16_U * w
        ratio = np.where(valid, err / np.maximum(bound, 1e-30), 0.0)
        return float(np.max(ratio)), float(np.max(np.where(valid, err, 0)))

    def native(fn, *args):
        hlo = jax.jit(fn).lower(*args).as_text()
        check("tpu_custom_call" in hlo,
              f"{fn.__name__} lowers to a native Mosaic kernel")
        return fn(*args)

    # decode: 8 rows, ragged lengths up to the serving envelope
    lengths = np.asarray([1, 17, 128, 300, 511, 640, 1000, 1088], np.int32)
    tab = tables_for(lengths, 128)
    q = jax.random.normal(kq, (len(lengths), hq, hd),
                          jnp.float32).astype(jnp.bfloat16)
    lens = jnp.asarray(lengths)

    def paged_attention(q, kpg, vpg, tab, lens):
        return ops.paged_attention(q, kpg, vpg, tab, lens, impl="pallas")

    args = (q, k_pages, v_pages, tab, lens)
    got = native(paged_attention, *args)
    ratio, err = gap(got, paged_attention_ref, args)
    print(f"  paged_attention: B=8 Hq={hq} Hkv={hkv} hd={hd} bs={bs} bf16, "
          f"max |kernel - ref| {err:.3e}, worst element at {ratio:.3f} of "
          f"its bound 2^-7 |ref| + 2^-8 w")
    check(ratio <= 1.0 and bool(jnp.all(jnp.isfinite(got))),
          "paged_attention matches its reference")

    # chunked prefill: suffixes at cached-prefix offsets, one padded bucket
    starts = np.asarray([0, 16, 512, 0], np.int32)
    sfx = np.asarray([128, 100, 512, 1024], np.int32)
    lq = 1024
    tab = tables_for(starts + sfx, 128)
    q = jax.random.normal(kp, (len(starts), lq, hq, hd),
                          jnp.float32).astype(jnp.bfloat16)
    st, ln = jnp.asarray(starts), jnp.asarray(sfx)

    def chunked_prefill(q, kpg, vpg, tab, st, ln):
        return ops.chunked_prefill(q, kpg, vpg, tab, st, ln, impl="pallas")

    args = (q, k_pages, v_pages, tab, st, ln)
    got = native(chunked_prefill, *args)
    valid = (np.arange(lq)[None, :] < sfx[:, None])[..., None, None]
    ratio, err = gap(got, chunked_prefill_ref, args, valid)
    pad_zero = bool(np.all(np.where(valid, 0.0, np.asarray(got,
                                                          np.float32)) == 0))
    print(f"  chunked_prefill: B=4 L={lq} starts={starts.tolist()} "
          f"lens={sfx.tolist()} bf16, max |kernel - ref| {err:.3e}, worst "
          f"element at {ratio:.3f} of its bound 2^-7 |ref| + 2^-8 w")
    check(ratio <= 1.0 and pad_zero
          and bool(jnp.all(jnp.isfinite(got))),
          "chunked_prefill matches its reference, padding rows are zero")


# ---------------------------------------------------------------------------
# (c) stability at a real layer width
# ---------------------------------------------------------------------------

def phase_stability(cfg, seed, *, k=8192, cond=1e9):
    """W (m x n) = the down projection's matrix view, X (n x k) with
    log-spaced singular values from 1 to 1/cond, at the rank compression
    gives that layer at ratio 0.6."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import baselines, coala
    from repro.models.linear import rank_for_ratio

    n, m = cfg.d_ff, cfg.d_model
    rank = rank_for_ratio(n, m, 0.6)
    t0 = time.perf_counter()
    rng = np.random.RandomState(seed)
    u = np.linalg.qr(rng.randn(n, n))[0]
    v = np.linalg.qr(rng.randn(k, n))[0]
    x64 = (u * np.logspace(0, -np.log10(cond), n)[None, :]) @ v.T
    w64 = rng.randn(m, n) / np.sqrt(n)
    w = jnp.asarray(w64, jnp.float32)
    x = jnp.asarray(x64, jnp.float32)
    # float64 truth from the float32 inputs the device sees
    wt, xt = np.asarray(w, np.float64), np.asarray(x, np.float64)
    ur = np.linalg.svd(wt @ xt, full_matrices=False)[0][:, :rank]
    w_ref = ur @ (ur.T @ wt)
    ref_norm = np.linalg.norm(w_ref, 2)

    def rel(w_apx):
        w_apx = np.asarray(w_apx, np.float64)
        return float(np.linalg.norm(w_apx - w_ref, 2) / ref_norm)

    def gram_path():
        a, b = baselines.svd_llm_v2(w, x @ x.T, rank)
        return rel(a @ b)

    print(f"  W {m}x{n}, X {n}x{k}, cond(X) {cond:.0e}, rank {rank}; "
          f"host truth in {time.perf_counter() - t0:.1f} s", flush=True)

    def timed(label, fn):
        t = time.perf_counter()
        err = fn()
        print(f"  {label}: {err:.3e} ({time.perf_counter() - t:.1f} s)",
              flush=True)
        return err

    # where the default precision loses accuracy: the QR of X^T (calibration)
    # and the SVD solve plus the A B product (compression), each at default
    # and at "highest"; (default, default) is the code without the scope
    errs = {}
    for qr_p in (None, "highest"):
        t = time.perf_counter()
        with jax.default_matmul_precision(qr_p):
            r_f = jax.block_until_ready(coala.r_from_x(x))
        print(f"  QR of X^T at {qr_p or 'default'}: "
              f"{time.perf_counter() - t:.1f} s", flush=True)
        for solve_p in (None, "highest"):
            def solve():
                with jax.default_matmul_precision(solve_p):
                    a, b = coala._factor_from_r(w, r_f, rank)
                    return rel(a @ b)
            errs[qr_p, solve_p] = timed(
                f"COALA error, QR at {qr_p or 'default'}, solve at "
                f"{solve_p or 'default'}", solve)
    without = errs[None, None]
    with_scope = timed("COALA with the scope (coala_project)",
                       lambda: rel(coala.coala_project(w, x, rank=rank)))
    with jax.default_matmul_precision("highest"):
        gram_with = timed("Gram path with the scope", gram_path)
    print(f"  COALA error without the scope {without:.3e}, with it "
          f"{with_scope:.3e}; Gram path with it {gram_with:.3e}")
    check(with_scope < COALA_MAX_ERR,
          f"COALA error {with_scope:.3e} < {COALA_MAX_ERR}")
    check(not np.isfinite(gram_with) or gram_with >= GRAM_MIN_MARGIN
          * with_scope, f"Gram path >= {GRAM_MIN_MARGIN:g}x worse than COALA")


# ---------------------------------------------------------------------------
# (d) calibrate -> compress -> serve
# ---------------------------------------------------------------------------

def calib_batches(cfg, seed):
    from repro.data import DataConfig, TokenPipeline
    pipe = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size,
                                    seq_len=CALIB_SEQ,
                                    global_batch=CALIB_ROWS, seed=seed), cfg)
    return [pipe.get_batch(i) for i in range(CALIB_BATCHES)]


def phase_calibrate(model, params, batches):
    import jax
    from repro.core.calibrate import calibrate_model
    cal = calibrate_model(model, params, batches)
    rf = jax.block_until_ready(cal.r_factors())
    tokens = cal.tokens_seen()
    print(f"  {len(rf)} layers, {min(tokens.values())} tokens each "
          f"(widest layer n={max(r.shape[0] for r in rf.values())})")
    return cal


def phase_compress(model, params, cal):
    import jax
    from repro.config import CompressConfig
    from repro.core.compress import compress_model, compression_summary
    from repro.obs import numerics
    ccfg = CompressConfig(method="coala", ratio=0.6, lam=4.0, mu=-1.0)
    cparams, reports = compress_model(model, params, cal, ccfg)
    jax.block_until_ready(cparams)
    s = compression_summary(reports)
    print(f"  {s['layers']} layers, kept {s['kept_ratio']:.3f} of their "
          f"parameters, mean weighted error {s['mean_rel_err']:.4f}")
    health = numerics.check_compression(reports)
    worst = max(health, key=lambda h: h.residual / max(h.bound, 1e-12))
    print(f"  worst residual {worst.residual:.4e} vs bound "
          f"{worst.bound:.4e} ({worst.residual / worst.bound:.4f}x) at "
          f"{worst.path}")
    check(len(health) == s["layers"] and numerics.worst_level(health)
          == numerics.OK, "every layer's residual is under "
          f"{numerics.NumericsPolicy().warn_residual_excess:g}x its bound")
    return cparams


def trace_prompts(cfg, seed):
    import numpy as np
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, (int(t),)).astype(np.int32)
            for t in np.linspace(128, 1024, MAX_RUNNING)]


def phase_serve(name, model, params, prompts):
    from repro.serve import ContinuousEngine
    max_len = max(len(p) for p in prompts) + NEW_TOKENS
    # decode batches pad to 8 rows and prefill suffixes to the prompt
    # buckets; random prompts share no prefix, and the prefix cache would
    # triple the warmup set (one envelope per cached offset)
    eng = ContinuousEngine(model, params, block_size=BLOCK_SIZE,
                           num_blocks=1024, max_running=MAX_RUNNING,
                           bucket_sizes=(MAX_RUNNING,),
                           prefill_bucket_sizes=(128, 256, 512, 1024,
                                                 max_len),
                           prefix_cache=False)
    check(eng.paged_kernel and eng.prefill_kernel,
          f"[{name}] engine serves through paged_attention and "
          "chunked_prefill")
    w = eng.warmup(max_len=max_len)
    print(f"  [{name}] warmup {w['warmup_seconds']:.1f} s for "
          f"{int(w['decode_signatures'])} decode + "
          f"{int(w['prefill_signatures'])} prefill signatures")
    ids = [eng.submit(p, NEW_TOKENS) for p in prompts]
    fin = {r.req_id: r for r in eng.run()}
    m = eng.metrics()
    done = [fin[i] for i in ids if i in fin]
    print(f"  [{name}] {len(done)} requests, {m['new_tokens']} new tokens, "
          f"{m['decode_steps']} decode steps, {m['prefill_batches']} "
          f"prefill calls, {m['post_warmup_compiles']} post-warmup compiles")
    check(len(done) == len(prompts)
          and all(len(r.out_tokens) == NEW_TOKENS for r in done),
          f"[{name}] all {len(prompts)} requests finished with "
          f"{NEW_TOKENS} tokens")
    check(all(r.logits_finite for r in done),
          f"[{name}] every sampled logit was finite")
    check(m["post_warmup_compiles"] == 0, f"[{name}] 0 post-warmup compiles")


# ---------------------------------------------------------------------------
# four chips: sharded calibration vs single device
# ---------------------------------------------------------------------------

def phase_sharded(model, params, batches, n_shards):
    import jax
    import numpy as np
    from repro.core.calibrate import calibrate_model
    from repro.dist.calibrate import calibrate_sharded
    single = calibrate_model(model, params, batches).r_factors()
    mesh = jax.make_mesh((n_shards,), ("data",),
                         devices=jax.devices()[:n_shards])
    sharded = calibrate_sharded(model, params, batches, mesh)
    rd = jax.block_until_ready(sharded.r_factors())
    for i, d in enumerate(sharded.shard_devices):
        print(f"  shard {i}: R held on {d}")
    check(len(set(sharded.shard_devices)) == n_shards,
          f"the {n_shards} shards' R factors sit on {n_shards} distinct "
          "devices")
    worst, where = 0.0, ""
    for p, rs in single.items():
        gs = np.asarray(rs, np.float64).T @ np.asarray(rs, np.float64)
        r = np.asarray(rd[p], np.float64)
        e = float(np.linalg.norm(r.T @ r - gs) / np.linalg.norm(gs))
        if e > worst:
            worst, where = e, p
    print(f"  {len(single)} layers, worst R^T R mismatch {worst:.3e} "
          f"at {where}")
    check(worst < PARITY_MAX, f"sharded R matches single-device R "
          f"({worst:.3e} < {PARITY_MAX})")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only sharded calibration over data=4 against "
                         "single-device calibration (needs 4 chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro sources under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax
    from repro import compile_cache

    cache = compile_cache.enable()
    devices = jax.devices()
    dev = devices[0]
    print(f"# jax {jax.__version__}, {len(devices)} x {dev.platform} "
          f"({dev.device_kind}), compile cache {cache}", flush=True)
    # (a) JAX falls back to the CPU when the TPU fails to start
    if dev.platform != "tpu":
        print(f"chip_smoke: FAILED: first device is {dev.platform}, "
              "not a TPU", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: FAILED: {need} chips needed, {len(devices)} "
              "found", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config(ARCH)
    phases = Phases(jax)
    model = build_model(cfg)
    params = phases.run("init", lambda: jax.block_until_ready(
        model.init(jax.random.PRNGKey(args.seed))))
    batches = calib_batches(cfg, args.seed)
    if args.four_chips:
        phases.run("sharded calibration",
                   phase_sharded, model, params, batches, 4)
    else:
        phases.run("b kernels", phase_kernels, cfg, args.seed)
        phases.run("c stability", phase_stability, cfg, args.seed)
        cal = phases.run("d calibrate", phase_calibrate, model, params,
                         batches)
        cparams = phases.run("d compress", phase_compress, model, params,
                             cal)
        prompts = trace_prompts(cfg, args.seed)
        phases.run("d serve dense", phase_serve, "dense", model, params,
                   prompts)
        phases.run("d serve coala", phase_serve, "coala", model, cparams,
                   prompts)
    print(f"# total compile {phases.compile_s:.1f} s, "
          f"{phases.cache_hits} persistent-cache hits", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
