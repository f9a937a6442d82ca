"""Traffic kind ``calib_moe``: streaming COALA calibration of a
DeepSeek-V2-style MoE model at one chip's expert share.

The configuration file holds the model's HF keys (``n_routed_experts`` the
experts held here, from ``expert_start``; ``router_experts`` the router's
width), from which this module builds the program's ``ModelConfig``; the
weights are drawn here, on the device, from ``--seed``. Seeded
``TokenPipeline`` batches of ``batch`` x ``seq_len`` token ids from the
configuration's vocabulary go through ``LM.capture_forward`` into a
``Calibrator``: every target linear's input folds into its R by QR,
``fold_rows`` rows a fold, and every held expert's routed rows into its own
R by the grouped fold, all under the mix's matmul ``precision`` in float32.
Set-up folds batch 0; the window then folds whole batches until
``--seconds`` have passed, each ended by ``block_until_ready`` on every R.
``calib_tok_s`` is the tokens of those batches over the window.

``correct``: as in ``kinds/calib.py``, every R factor must have gained, as
RᵀR, the Gram of the rows folded in the window, which the plain reference
(``reference/deepseek_v2.py``) recomputes layer by layer, a sequence at a
time; for an expert, the Gram of the rows routed to it. The reference
routes by its own router scores; a row whose top-k differs from the
program's (kept per MoE layer and batch) takes the program's choice where
the program's least expert lies within ``tie_logit_delta`` of the
reference's k-th in router logits (a near-tie: float32 rounding at
``"highest"`` moves router logits by ~1e-5 at most, and a true fault moves
them by far more), and counts as a routing fault otherwise. The numbers
compared are the worst relative gap over every stream (``fold_gram_gap``)
and the routing faults (``route_faults``); the near-ties are counted under
``info``. The control puts the reference one precision lower in the
program's place: the Grams of its own activations at three bfloat16 passes
(float32 at ``"high"``), routed by its own scores, stand for what the R
factors gained.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import generate
from benchmarks.chip.reference import calibration as ref_calib
from benchmarks.chip.reference import deepseek_v2 as ref

# the target linear read by each program path's last part -> reference tap
TAP_OF = {"wq": "attn_in", "wkv_a": "attn_in", "wkv_b": "latent",
          "wo": "attn_out", "gate": "mlp_in", "up": "mlp_in",
          "down": "mlp_hidden"}
# the keys of the configuration that the reference reads
ARCH_KEYS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
             "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
             "kv_lora_rank", "rms_norm_eps", "rope_theta", "rope_scaling",
             "num_experts_per_tok", "norm_topk_prob",
             "routed_scaling_factor", "first_k_dense_replace")
MODEL_KEYS = ("num_hidden_layers", "first_k_dense_replace", "hidden_size",
              "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
              "v_head_dim", "kv_lora_rank", "intermediate_size",
              "moe_intermediate_size", "n_shared_experts", "router_experts")


def model_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig`` from the configuration's HF keys (the
    program scales no routed weight: ``routed_scaling_factor`` must be 1)."""
    from repro.config import ModelConfig, MoEConfig, YarnConfig
    if float(conf["routed_scaling_factor"]) != 1.0:
        raise ValueError("the program applies no routed_scaling_factor; "
                         f"got {conf['routed_scaling_factor']}")
    y = conf["rope_scaling"]
    yarn = None if y is None else YarnConfig(
        factor=float(y["factor"]),
        original_max_position_embeddings=int(
            y["original_max_position_embeddings"]),
        beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
        mscale=float(y["mscale"]), mscale_all_dim=float(y["mscale_all_dim"]))
    dn, dr = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
    return ModelConfig(
        name=conf["name"], family="moe",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=dn + dr,
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        max_seq_len=conf["max_position_embeddings"],
        rope_theta=float(conf["rope_theta"]), yarn=yarn,
        norm_eps=float(conf["rms_norm_eps"]),
        kv_lora_rank=conf["kv_lora_rank"], qk_nope_dim=dn, qk_rope_dim=dr,
        v_head_dim=conf["v_head_dim"],
        moe=MoEConfig(num_experts=conf["router_experts"],
                      top_k=conf["num_experts_per_tok"],
                      num_shared=conf["n_shared_experts"],
                      d_ff_expert=conf["moe_intermediate_size"],
                      norm_topk_prob=bool(conf["norm_topk_prob"]),
                      e_start=conf["expert_start"],
                      e_count=conf["n_routed_experts"]),
        first_k_dense=conf["first_k_dense_replace"],
        tie_embeddings=bool(conf["tie_word_embeddings"]))


def arch(conf: Dict[str, Any]) -> Dict[str, Any]:
    """The reference's architecture: the configuration's HF keys."""
    out = {k: conf[k] for k in ARCH_KEYS}
    out["e_start"] = conf["expert_start"]
    return out


def draw_weights(model, seed31: int, dtype, init: Dict[str, Any]):
    """The program's parameter tree (``LM.init``, read as shapes only),
    every value drawn here in one jitted call: ``embed`` normal x
    ``embed_std``, a norm's ``scale`` normal x ``norm_scale_std`` (applied
    as 1 + scale), every other array (linears, the router, expert banks)
    normal / sqrt(d_in)."""
    shapes = jax.eval_shape(lambda k: model.init(k, dtype),
                            jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    rules = []
    for path, s in flat:
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "embed":
            std = float(init["embed_std"])
        elif name == "scale":
            std = float(init["norm_scale_std"])
        else:
            std = 1.0 / np.sqrt(s.shape[-2])
        rules.append((std, s.shape, s.dtype))

    @jax.jit
    def make(key):
        return jax.tree_util.tree_unflatten(treedef, [
            (jax.random.normal(jax.random.fold_in(key, i), shape,
                               jnp.float32) * std).astype(dt)
            for i, (std, shape, dt) in enumerate(rules)])

    return jax.block_until_ready(make(jax.random.PRNGKey(seed31)))


def layer_of(path: str) -> int:
    """``prefix/0/...`` -> 0; ``blocks/<r>/sub0/...`` -> 1 + r."""
    parts = path.split("/")
    return 0 if parts[0] == "prefix" else 1 + int(parts[1])


def tap_of(path: str) -> str:
    parts = path.split("/")
    if parts[-2].startswith("expert"):
        return "/".join(parts[-2:])
    return TAP_OF[parts[-1]]


@jax.jit
def _fold_gap(r_new, r_old, gram):
    """|| R_newᵀR_new - R_oldᵀR_old - G || / || G || (Frobenius)."""
    hp = jax.lax.Precision.HIGHEST
    gained = (jnp.matmul(r_new.T, r_new, precision=hp)
              - jnp.matmul(r_old.T, r_old, precision=hp))
    return jnp.linalg.norm(gained - gram) / jnp.linalg.norm(gram)


def _gap(r_new, r_old, gram) -> float:
    """The relative gap; where no row was folded (the Gram is zero), 0 if R
    did not move and 1 if it did."""
    if float(jnp.linalg.norm(gram)) == 0.0:
        return float(not np.array_equal(np.asarray(r_new),
                                        np.asarray(r_old)))
    return float(_fold_gap(r_new, r_old, gram))


def check(conf, params, window: List[np.ndarray], routes, r_open,
          r_close, delta: float, seq_len: int, quant=None) -> Dict[str, Any]:
    """Layer by layer, a sequence at a time, the reference Grams of the
    window's batches against what each R gained. Returns the worst gap,
    the routing faults and near-ties, and the worst distance of a
    differing route; with ``quant``, also the control's worst gap: the
    Grams of the reference's own activations at ``quant``, routed by its
    own scores, against the reference's."""
    a = arch(conf)
    items = ref.items(a)
    by_layer: Dict[int, List[str]] = {}
    for p in r_close:
        by_layer.setdefault(layer_of(p), []).append(p)
    xs = [[ref.embed(params["embed"], jnp.asarray(row, jnp.int32))
           for row in tokens] for tokens in window]
    xc = [list(seqs) for seqs in xs]
    out = {"fold_gram_gap": 0.0, "route_faults": 0, "near_ties": 0,
           "worst_route_distance": 0.0, "control_gap": 0.0}
    for i in range(a["num_hidden_layers"]):
        moe = ref.is_moe(a, i)
        lp = ref.layer_params(params, i)
        grams: Dict[str, jax.Array] = {}
        ctrl: Dict[str, jax.Array] = {}
        for b, seqs in enumerate(xs):
            for s, x in enumerate(seqs):
                forced = None
                if moe:
                    forced = routes[i][b][s * seq_len:(s + 1) * seq_len]
                seqs[s], g, flipped, dist = ref.layer_grams(
                    items, moe, None, lp, x, forced, delta)
                grams = g if not grams else ref_calib._add(grams, g)
                out["near_ties"] += int(jnp.sum(flipped))
                out["route_faults"] += int(jnp.sum(dist > delta))
                out["worst_route_distance"] = max(
                    out["worst_route_distance"], float(jnp.max(dist)))
                if quant is not None:
                    xc[b][s], g, _, _ = ref.layer_grams(
                        items, moe, quant, lp, xc[b][s], None, delta)
                    ctrl = g if not ctrl else ref_calib._add(ctrl, g)
        for tap, g in grams.items():
            norm = float(jnp.linalg.norm(g))
            paths = [p for p in by_layer.get(i, []) if tap_of(p) == tap]
            if not paths and norm > 0:               # rows never folded
                out["fold_gram_gap"] = max(out["fold_gram_gap"], 1.0)
            for p in paths:
                old = r_open.get(p)
                old = jnp.zeros_like(r_close[p]) if old is None \
                    else jnp.asarray(old)
                out["fold_gram_gap"] = max(out["fold_gram_gap"],
                                           _gap(r_close[p], old, g))
            if quant is not None and norm > 0:
                out["control_gap"] = max(out["control_gap"], float(
                    jnp.linalg.norm(ctrl[tap] - g)) / norm)
    return out


def run(**kw) -> Dict[str, Any]:
    """``_run`` with the program's routing kept: each MoE layer's
    ``ffn._route_held``, whose first output is every row's top-k experts,
    leaves that output in ``pending`` for the layer's record to take."""
    from repro.models import ffn
    route_held = ffn._route_held
    pending: List[jax.Array] = []

    def keep_routes(*args):
        out = route_held(*args)
        pending.append(out[0])
        return out

    ffn._route_held = keep_routes
    try:
        return _run(pending=pending, **kw)
    finally:
        ffn._route_held = route_held


def _run(*, conf, mix, seed, seconds, ann, tracer, compiles, control,
         t_start, pending: List[jax.Array]) -> Dict[str, Any]:
    from benchmarks.chip import harness
    from repro.core.calibrate import Calibrator
    from repro.data.pipeline import DataConfig, TokenPipeline
    from repro.models.common import ParallelCtx
    from repro.models.transformer import LM

    model = LM(model_config(conf))
    # attention in blocks of ``attn_block`` tokens (the program's chunked
    # path): the dense path would hold every head's 2048 x 2048 scores at
    # once, ~2 GB a copy
    blk = int(mix["attn_block"])
    ctx = ParallelCtx(dense_attn_max_seq=blk, attn_chunk_q=blk,
                      attn_chunk_kv=blk)

    class Routes(Calibrator):
        """The program's calibrator, keeping each MoE layer's routes (the
        rows' top-k, as the program's routing gave them just before the
        layer's record) and routed counts of every batch for the check."""

        def __init__(self, **kw):
            super().__init__(**kw)
            self.routes: Dict[int, List[jax.Array]] = {}
            self.counts: List[List[List[int]]] = []

        def record_experts(self, path, e_start, stacks, counts):
            super().record_experts(path, e_start, stacks, counts)
            layer = layer_of(path)
            self.routes.setdefault(layer, []).append(pending.pop())
            if layer == min(self.routes):
                self.counts.append([])
            self.counts[-1].append([int(c) for c in counts])

    dt = jnp.dtype(conf["dtypes"]["calibration"])
    params = draw_weights(model, generate.seed31(seed, 0), dt, conf["init"])
    pipe = TokenPipeline(DataConfig(
        vocab_size=conf["vocab_size"], seq_len=int(mix["seq_len"]),
        global_batch=int(mix["batch"]), seed=generate.seed31(seed, 3)))
    cal = Routes(dtype=dt, max_tokens_per_record=int(mix["fold_rows"]))
    precision = mix["precision"]

    def fold(step: int) -> Dict[str, jax.Array]:
        batch = pipe.get_batch(step)
        with jax.default_matmul_precision(precision):
            model.capture_forward(params, batch, cal, ctx=ctx,
                                  compute_dtype=dt)
        rs = {p: s.r for p, s in cal.streams.items()}
        jax.block_until_ready(rs)
        return rs

    rs = fold(0)
    # the opening R factors wait on the host: the device holds one set
    host: Dict[int, np.ndarray] = {}
    r_open = {p: host.setdefault(id(r), np.asarray(r)) for p, r in rs.items()}
    del rs, host
    cal.routes.clear()
    cal.counts.clear()
    before = compiles.snapshot()
    jax.config.update("jax_log_compiles", True)
    batches: List[Dict[str, float]] = []
    t0 = time.perf_counter()
    end = t0 + seconds
    tracer.start(batches)
    step = 1
    while True:
        b0 = time.perf_counter()
        # drop the last batch's R factors as the streams replace them: the
        # device holds one set (3.3 GB at the real size), not two
        rs = None
        with ann("calib.batch"):
            rs = fold(step)
        now = time.perf_counter()
        batches.append({"start": b0, "end": now})
        step += 1
        tracer.maybe_stop(batches)
        if now >= end:
            break
    close = time.perf_counter()
    tracer.maybe_stop(batches, force=True)
    after = compiles.snapshot()
    jax.config.update("jax_log_compiles", False)
    tokens_per_batch = int(mix["batch"]) * int(mix["seq_len"])
    n = len(batches)
    e2e = {"setup_s": t0 - t_start,
           "calib_tok_s": n * tokens_per_batch / (close - t0)}
    device = harness.device_info()
    # one fold per distinct dense input of a layer (W_q and W_kv_a share
    # theirs, as do gate and up), for the mfu reader
    inputs = {(layer_of(p), tap_of(p)): int(r.shape[-1])
              for p, r in rs.items() if "/expert" not in p}
    record = {"batches": batches[tracer.first_step:tracer.last_step],
              "expert_counts": cal.counts[tracer.first_step:tracer.last_step],
              "model": {k: conf[k] for k in MODEL_KEYS},
              "tokens_per_batch": tokens_per_batch,
              "fold_rows": int(cal.max_tokens),
              "fold_widths": sorted(inputs.values()),
              "host_window": (tracer.t_start, tracer.t_stop)}
    info = {"setup_compiles": before, "window_s": close - t0, "batches": n,
            "window_compiles": after["compiles"] - before["compiles"],
            "window_cache_hits": after["cache_hits"] - before["cache_hits"],
            "routed_rows": [sum(sum(c) for c in b) for b in cal.counts],
            "expert_rows_max": [max(max(c) for c in b) for b in cal.counts]}
    window = [np.asarray(pipe.get_batch(k)["tokens"]) for k in
              range(1, n + 1)]
    routes = {i: v for i, v in cal.routes.items()}
    cal.reset()
    seq_len = int(mix["seq_len"])
    delta = float(mix["tie_logit_delta"])
    got = check(conf, params, window, routes, r_open, rs, delta, seq_len,
                mix["control"] if control else None)
    info.update(near_ties=got["near_ties"],
                worst_route_distance=got["worst_route_distance"])
    compared = {"fold_gram_gap": got["fold_gram_gap"],
                "route_faults": got["route_faults"]}
    if control:
        info["program_fold_gram_gap"] = compared["fold_gram_gap"]
        compared["fold_gram_gap"] = got["control_gap"]
    return {"e2e": e2e, "record": record, "attempted": n, "failed": 0,
            "device": device, "compared": compared, "info": info}
