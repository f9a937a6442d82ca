"""Model-wide COALA compression driver.

Walks the parameter pytree, and for every compressible linear with a
calibrated R factor solves the context-aware low-rank problem (COALA
Algorithm 1/2, or a baseline for comparison) and swaps ``{"w": ...}`` for the
factored ``{"b_t", "a_t"}`` pair the model substrate executes natively
(including the fused Pallas ``lowrank_linear`` kernel on TPU).

Per-layer μ follows the paper's Eq. (5) with a global λ — essential because
layer norms vary by orders of magnitude across depth (paper Fig. 4).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import CompressConfig
from repro.core import baselines as bl
from repro.core import coala as coala_lib
from repro.core.precision import highest_precision
from repro.core.theory import optimal_weighted_error
from repro.models.linear import rank_for_ratio

# layer-name roles eligible for compression (paper compresses Q,K,V,O,Up,Down
# projections; embeddings / lm_head / routers / norms / recurrence params stay)
COMPRESSIBLE_KEYS = {"wq", "wk", "wv", "wo", "up", "gate", "down",
                     "in_proj", "out_proj", "ff_up", "ff_down",
                     "wkv_a", "wkv_b", "shared"}
MIN_DIM = 32


def rank_for_ratio_dims(d_in: int, d_out: int, ratio: float) -> int:
    return rank_for_ratio(d_in, d_out, ratio)


def compressible(path: Tuple[str, ...], shape, cfg=None) -> bool:
    """Is the linear at ``path`` (to its dict or its 'w' leaf) a target?"""
    names = [str(p) for p in path]
    if names and names[-1] == "w":
        names = names[:-1]
    key = names[-1] if names else ""
    if key not in COMPRESSIBLE_KEYS - {"shared"}:
        return False
    d_in, d_out = shape[-2], shape[-1]
    return min(d_in, d_out) >= MIN_DIM


@dataclasses.dataclass
class LayerReport:
    path: str
    rank: int
    mu: float
    rel_err_weighted: float      # ||(W-W')R^T||/||W R^T||
    params_before: int
    params_after: int
    # attainable minimum of the same ratio (Σ-tail of σ(W Rᵀ), theory.py's
    # optimal_weighted_error / ||W Rᵀ||); nan when the layer had no R factor.
    # obs/numerics.check_compression grades rel_err_weighted against this.
    rel_err_bound: float = float("nan")


def _solve(w_mat, r_factor, rank, ccfg: CompressConfig):
    """Dispatch on method. w_mat: (d_out, d_in) matrix view."""
    if ccfg.method == "coala":
        res = coala_lib.coala_factors(
            w_mat, r_factor=r_factor, rank=rank,
            mu=max(ccfg.mu, 0.0) if ccfg.mu >= 0 else 0.0,
            lam=ccfg.lam if ccfg.mu < 0 else None,
            use_rsvd=ccfg.use_rsvd, rsvd_oversample=ccfg.rsvd_oversample,
            rsvd_power_iters=ccfg.rsvd_power_iters)
        return res.a, res.b, res.mu
    if ccfg.method == "svd":
        a, b = bl.plain_svd(w_mat, rank)
        return a, b, 0.0
    if ccfg.method == "svd_llm":
        gram = r_factor.T @ r_factor
        a, b = bl.svd_llm(w_mat, gram, rank)
        return a, b, 0.0
    if ccfg.method == "svd_llm_v2":
        gram = r_factor.T @ r_factor
        a, b = bl.svd_llm_v2(w_mat, gram, rank)
        return a, b, 0.0
    if ccfg.method == "asvd":
        # diagonal scale from R (mean |col| proxy for mean |activation|)
        a, b = bl.asvd(w_mat, r_factor.T, rank)
        return a, b, 0.0
    raise ValueError(f"unknown method {ccfg.method}")


def compress_params(params, r_factors: Dict[str, jax.Array],
                    ccfg: CompressConfig, rank_map=None, e_start: int = 0):
    """Returns (new_params, [LayerReport...]). ``r_factors`` keys are the
    calibrator paths ('blocks/3/sub0/mixer/wq', ...). ``rank_map`` (adaptive
    allocation, core/rank_alloc.py) overrides the uniform ratio per path.
    An expert bank's row ``e`` is expert ``e_start + e`` (the share held)."""
    reports = []

    def _compress_experts(node, path):
        """Per-expert COALA (paper's limited-data regime: each expert sees
        only its routed tokens — μ-regularization is load-bearing here).
        Dense stacks (E, d_in, d_out) become factored tuples
        (b_t (E,d_in,r), a_t (E,r,d_out))."""
        p = "/".join(path)
        out = dict(node)
        e_total = node["w_gate"].shape[0]
        for mat, rf_kind in (("w_gate", "in"), ("w_up", "in"),
                             ("w_down", "hid")):
            w_stack = node[mat]
            if isinstance(w_stack, tuple) or w_stack.ndim != 3:
                continue
            bts, ats = [], []
            compressed_any = False
            for e in range(e_total):
                rf = r_factors.get(f"{p}/expert{e_start + e}/{rf_kind}")
                w = w_stack[e]
                d_in, d_out = w.shape
                rank = (ccfg.rank if ccfg.rank > 0
                        else rank_for_ratio(d_in, d_out, ccfg.ratio))
                rank = min(rank, min(d_in, d_out))
                if rf is None:
                    # expert never routed to during calibration: keep the
                    # EYM projection (X=I ⇒ μ-regularized limit, Prop. 3)
                    a, b = bl.plain_svd(w.T.astype(jnp.float32), rank)
                else:
                    a, b, mu = _solve(w.T.astype(jnp.float32),
                                      rf.astype(jnp.float32), rank, ccfg)
                    compressed_any = True
                bts.append(b.T.astype(w.dtype))
                ats.append(a.T.astype(w.dtype))
                if rf is None:
                    rel_err = bound = float("nan")
                else:
                    den = jnp.maximum(jnp.linalg.norm(w.T @ rf.T), 1e-9)
                    rel_err = float(
                        jnp.linalg.norm((w.T - a @ b) @ rf.T) / den)
                    bound = float(optimal_weighted_error(
                        w.T.astype(jnp.float32), rf.T.astype(jnp.float32),
                        rank) / den)
                reports.append(LayerReport(
                    path=f"{p}/{mat}/e{e}", rank=rank,
                    mu=0.0, rel_err_weighted=rel_err,
                    params_before=d_in * d_out,
                    params_after=rank * (d_in + d_out),
                    rel_err_bound=bound))
            out[mat] = (jnp.stack(bts), jnp.stack(ats))
        return out

    def walk(node, path):
        if isinstance(node, dict):
            if ("w_gate" in node and not isinstance(node["w_gate"], tuple)
                    and getattr(node["w_gate"], "ndim", 0) == 3
                    and any(k.startswith("/".join(path) + "/expert")
                            for k in r_factors)):
                sub = _compress_experts(node, path)
                # shared experts / router handled by the normal walk below
                return {k: (v if k in ("w_gate", "w_up", "w_down")
                            else walk(v, path + [k]))
                        for k, v in sub.items()}
            if "w" in node and getattr(node["w"], "ndim", 0) == 2:
                p = "/".join(path)
                if p in r_factors and compressible(tuple(path) + ("w",),
                                                   node["w"].shape):
                    w = node["w"]
                    d_in, d_out = w.shape
                    w_mat = w.T.astype(jnp.float32)       # (d_out, d_in)
                    if rank_map is not None and p in rank_map:
                        rank = rank_map[p]
                    else:
                        rank = (ccfg.rank if ccfg.rank > 0
                                else rank_for_ratio(d_in, d_out, ccfg.ratio))
                    rank = min(rank, min(d_in, d_out))
                    r_f = r_factors[p].astype(jnp.float32)
                    a, b, mu = _solve(w_mat, r_f, rank, ccfg)
                    num = jnp.linalg.norm((w_mat - a @ b) @ r_f.T)
                    den = jnp.maximum(jnp.linalg.norm(w_mat @ r_f.T), 1e-9)
                    reports.append(LayerReport(
                        path=p, rank=rank, mu=float(mu),
                        rel_err_weighted=float(num / den),
                        params_before=d_in * d_out,
                        params_after=rank * (d_in + d_out),
                        rel_err_bound=float(optimal_weighted_error(
                            w_mat, r_f.T, rank) / den)))
                    return {"b_t": b.T.astype(w.dtype),
                            "a_t": a.T.astype(w.dtype)}
                return {k: walk(v, path + [k]) for k, v in node.items()}
            return {k: walk(v, path + [k]) for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v, path + [str(i)]) for i, v in enumerate(node)]
        return node

    new_params = walk(params, [])
    return new_params, reports


def rank_map_from_reports(reports) -> Dict[str, int]:
    """Pin per-layer ranks from a previous compression's reports, keyed by
    full calibrator path. Recompressing with this map is *shape-stable*:
    the new factors have identical shapes/dtypes to the old ones, which is
    what live hot-swaps (serve/recalibrate.py) rely on to hit the serving
    engine's existing jit cache entries. Per-expert rows (path suffix
    '/e<i>') describe stacked expert banks, not standalone linears, and
    are skipped — expert ranks re-derive from the same ccfg."""
    import re
    return {r.path: r.rank for r in reports
            if not re.search(r"/e\d+$", r.path)}


@highest_precision
def compress_model(model, params, calibrator, ccfg: CompressConfig, *,
                   rank_map: Optional[Dict[str, int]] = None):
    """End-to-end: calibrator R factors -> compressed params + report.

    The calibrator keys look like 'blocks/2/sub0/mixer/wq'; stacked block
    params are compressed per-layer by slicing rep r, compressing, and
    re-stacking (each rep has its own activations, as in the paper).
    ``rank_map`` (full paths -> rank) overrides both the uniform ratio and
    adaptive allocation — recompression passes pin it from the previous
    reports (``rank_map_from_reports``) so factor shapes stay stable."""
    r_factors = calibrator.r_factors()
    if rank_map is None and getattr(ccfg, "adaptive_rank", False):
        from repro.core.rank_alloc import adaptive_rank_map
        weights = {}

        def collect(node, path):
            if isinstance(node, dict):
                if "w" in node and getattr(node["w"], "ndim", 0) == 2:
                    p = "/".join(path)
                    if p in r_factors and compressible(
                            tuple(path) + ("w",), node["w"].shape):
                        weights[p] = node["w"]
                    return
                for k, v in node.items():
                    collect(v, path + [k])
            elif isinstance(node, list):
                for i, v in enumerate(node):
                    collect(v, path + [str(i)])

        # stacked layers contribute per-rep entries keyed like the calibrator
        for skey in (k for k in ("blocks", "enc", "dec") if k in params):
            n_rep = jax.tree.leaves(params[skey])[0].shape[0]
            for r in range(n_rep):
                collect(jax.tree.map(lambda a: a[r], params[skey]),
                        [skey, str(r)])
        collect({k: v for k, v in params.items()
                 if k not in ("blocks", "enc", "dec")}, [])
        rank_map = adaptive_rank_map(weights, r_factors, ccfg.ratio)
    stacked_keys = [k for k in ("blocks", "enc", "dec") if k in params]

    # split stacked-layer paths ('<key>/<rep>/...') from flat paths
    flat_rf = {p: r for p, r in r_factors.items()
               if p.split("/", 1)[0] not in stacked_keys}
    per_key_rf: Dict[str, Dict[int, Dict[str, jax.Array]]] = {}
    for p, r in r_factors.items():
        head = p.split("/", 1)[0]
        if head in stacked_keys:
            _, rep, rest = p.split("/", 2)
            per_key_rf.setdefault(head, {}).setdefault(int(rep), {})[rest] = r

    reports = []
    new_params = dict(params)
    e_start = model.cfg.moe.e_start
    # non-stacked portions (prefix layers, top-level)
    np_, rep_ = compress_params(
        {k: v for k, v in params.items() if k not in stacked_keys},
        flat_rf, ccfg, rank_map=rank_map, e_start=e_start)
    new_params.update(np_)
    reports.extend(rep_)

    for skey in stacked_keys:
        blk_rf = per_key_rf.get(skey)
        if not blk_rf:
            continue
        n_rep = jax.tree.leaves(params[skey])[0].shape[0]
        slices = []
        for r in range(n_rep):
            blk = jax.tree.map(lambda a: a[r], params[skey])
            sub_map = None
            if rank_map is not None:
                pre = f"{skey}/{r}/"
                sub_map = {p[len(pre):]: v for p, v in rank_map.items()
                           if p.startswith(pre)}
            nb, rp = compress_params(blk, blk_rf.get(r, {}), ccfg,
                                     rank_map=sub_map, e_start=e_start)
            for item in rp:
                item.path = f"{skey}/{r}/" + item.path
            reports.extend(rp)
            slices.append(nb)
        new_params[skey] = jax.tree.map(lambda *xs: jnp.stack(xs), *slices)
    return new_params, reports


def compress_model_pair(model, params, calibrator, ccfg: CompressConfig, *,
                        draft_ratio: float):
    """Target + draft compression from ONE calibration pass.

    Self-speculative serving compresses the same model twice — the serving
    target at ``ccfg.ratio`` and a harder-compressed draft at ``draft_ratio``
    — and both solves reuse the calibrator's R factors, so the activation
    pass over the calibration data is paid once. Returns
    ``(target_params, draft_params, target_reports, draft_reports)``."""
    if not 0.0 < draft_ratio < 1.0:
        raise ValueError(f"draft_ratio must be in (0, 1), got {draft_ratio}")
    tparams, treports = compress_model(model, params, calibrator, ccfg)
    dcfg = dataclasses.replace(ccfg, ratio=draft_ratio, rank=0)
    dparams, dreports = compress_model(model, params, calibrator, dcfg)
    return tparams, dparams, treports, dreports


def compression_summary(reports) -> dict:
    before = sum(r.params_before for r in reports)
    after = sum(r.params_after for r in reports)
    errs = [r.rel_err_weighted for r in reports]
    return {"layers": len(reports),
            "params_before": before, "params_after": after,
            "kept_ratio": after / before if before else 1.0,
            "mean_rel_err": float(jnp.mean(jnp.asarray(errs))) if errs else 0.0,
            "max_rel_err": float(jnp.max(jnp.asarray(errs))) if errs else 0.0}
