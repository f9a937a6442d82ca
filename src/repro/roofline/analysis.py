"""Roofline analysis from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), in seconds:

  compute    = HLO_FLOPs_per_device / PEAK_FLOPS
  memory     = HLO_bytes_per_device / HBM_BW
  collective = Σ link_bytes_per_device(op) / ICI_BW

``cost_analysis()`` on the SPMD-partitioned module is per-device (verified
against a hand-checked matmul). Collective link bytes use ring-algorithm
costs parsed from the compiled HLO text:

  all-reduce:         2·(s-1)/s · result_bytes
  all-gather:           (s-1)/s · result_bytes        (result = gathered)
  reduce-scatter:       (s-1)   · result_bytes        (input = s · result)
  all-to-all:           (s-1)/s · result_bytes
  collective-permute:             result_bytes

where s = replica-group size parsed from the op. Hardware: TPU v5e-like —
197 TFLOP/s bf16, 819 GB/s HBM, 50 GB/s ICI per chip.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, Optional

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?P<shapes>.*?)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?P<start>-start|-done)?\(", re.M)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUP_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
_GROUP_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_WHILE_RE = re.compile(
    r"while\([^)]*\),\s*condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$")
_CONST_RE = re.compile(r"=\s*s32\[\]\s*constant\((\d+)\)")


def _shape_bytes(shapes_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shapes_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUP_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUP_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 2


def _split_computations(hlo_text: str) -> Dict[str, list]:
    """computation name -> list of body lines. Entry computation keyed as
    '__entry__' too."""
    comps: Dict[str, list] = {}
    cur: Optional[str] = None
    entry: Optional[str] = None
    for line in hlo_text.splitlines():
        if cur is None:
            m = _COMP_RE.match(line)
            if m and line.rstrip().endswith("{"):
                cur = m.group(1)
                comps[cur] = []
                if line.lstrip().startswith("ENTRY"):
                    entry = cur
        else:
            if line.startswith("}"):
                cur = None
            else:
                comps[cur].append(line)
    if entry:
        comps["__entry__"] = comps[entry]
    return comps


def _trip_count(cond_lines: list) -> int:
    """Trip count of a scan-style while: the max s32 scalar constant in the
    condition computation (induction starts at 0, compares LT bound)."""
    consts = []
    for line in cond_lines:
        consts += [int(c) for c in _CONST_RE.findall(line)]
    return max(consts) if consts else 1


def parse_collectives(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Per collective kind: count, result bytes, effective link bytes.

    Walks the computation graph hierarchically and multiplies collectives
    inside ``while`` bodies (lax.scan) by the loop trip count — XLA's flat
    text would otherwise count per-layer collectives once.
    """
    comps = _split_computations(hlo_text)
    out: Dict[str, Dict[str, float]] = {}

    def visit(comp_name: str, mult: float, seen):
        if comp_name not in comps or comp_name in seen:
            return
        seen = seen | {comp_name}
        for line in comps[comp_name]:
            m = _COLL_RE.match(line)
            if m and m.group("start") != "-done":
                op = m.group("op")
                rb = _shape_bytes(m.group("shapes"))
                s = _group_size(line)
                if op == "all-reduce":
                    link = 2.0 * (s - 1) / s * rb
                elif op == "all-gather":
                    link = (s - 1) / s * rb
                elif op == "reduce-scatter":
                    link = float(s - 1) * rb
                elif op == "all-to-all":
                    link = (s - 1) / s * rb
                else:  # collective-permute
                    link = float(rb)
                d = out.setdefault(op, {"count": 0, "result_bytes": 0.0,
                                        "link_bytes": 0.0})
                d["count"] += mult
                d["result_bytes"] += mult * rb
                d["link_bytes"] += mult * link
            w = _WHILE_RE.search(line)
            if w:
                cond, body = w.group(1), w.group(2)
                trips = _trip_count(comps.get(cond, []))
                visit(body, mult * trips, seen)

    visit("__entry__", 1.0, frozenset())
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    flops_per_dev: float
    bytes_per_dev: float
    collective_link_bytes: float
    collectives: Dict[str, Dict[str, float]]
    model_flops_global: float
    n_devices: int
    memory_stats: Dict[str, float]

    @property
    def t_compute(self) -> float:
        return self.flops_per_dev / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.bytes_per_dev / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.collective_link_bytes / ICI_BW

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / global HLO flops — fraction of compiled compute
        that is 'useful' model math (catches remat/redundancy waste)."""
        total = self.flops_per_dev * self.n_devices
        return self.model_flops_global / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Useful-FLOP utilization at the bound: (model flops / peak) over
        the dominant term's time — the score we hillclimb."""
        t_bound = max(self.t_compute, self.t_memory, self.t_collective)
        if t_bound <= 0:
            return 0.0
        t_useful = (self.model_flops_global / self.n_devices) / PEAK_FLOPS
        return t_useful / t_bound

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d.update(t_compute=self.t_compute, t_memory=self.t_memory,
                 t_collective=self.t_collective, dominant=self.dominant,
                 useful_ratio=self.useful_ratio,
                 roofline_fraction=self.roofline_fraction)
        return d


def analyze(compiled, *, arch: str, shape: str, mesh_name: str,
            n_devices: int, model_flops_global: float,
            jaxpr_cost: Optional[Dict[str, float]] = None) -> Roofline:
    """``jaxpr_cost`` (from roofline.jaxpr_cost.trace_cost) supplies
    scan-aware global flops/bytes; XLA's cost_analysis counts while bodies
    once and is kept only as a diagnostic."""
    ca = compiled.cost_analysis()
    hlo_flops_once = float(ca.get("flops", 0.0))
    hlo_bytes_once = float(ca.get("bytes accessed", 0.0))
    if jaxpr_cost is not None:
        flops = float(jaxpr_cost["flops"]) / n_devices
        byts = float(jaxpr_cost["bytes"]) / n_devices
    else:
        flops, byts = hlo_flops_once, hlo_bytes_once
    # NB: the SPMD module is per-device, so collective shapes (and hence link
    # bytes) are already per-device quantities — no division by n_devices.
    colls = parse_collectives(compiled.as_text())
    link_bytes = sum(v["link_bytes"] for v in colls.values())
    try:
        ms = compiled.memory_analysis()
        mem = {"argument_bytes": ms.argument_size_in_bytes,
               "output_bytes": ms.output_size_in_bytes,
               "temp_bytes": ms.temp_size_in_bytes,
               "alias_bytes": ms.alias_size_in_bytes,
               "code_bytes": ms.generated_code_size_in_bytes}
    except Exception:
        mem = {}
    return Roofline(arch=arch, shape=shape, mesh=mesh_name,
                    flops_per_dev=flops, bytes_per_dev=byts,
                    collective_link_bytes=link_bytes, collectives=colls,
                    model_flops_global=model_flops_global,
                    n_devices=n_devices, memory_stats=mem)


# ---------------------------------------------------------------------------
# MODEL_FLOPS (6·N·D for train, 2·N·D for inference; MoE uses active params)
# ---------------------------------------------------------------------------

def count_params(tree) -> int:
    import jax
    return sum(int(x.size) for x in jax.tree.leaves(tree))


def active_params(cfg, params_tree) -> float:
    """Total params minus the inactive routed-expert fraction."""
    import jax
    total = count_params(params_tree)
    if not cfg.uses_moe:
        return float(total)
    routed = 0
    flat = jax.tree_util.tree_flatten_with_path(params_tree)[0]
    for path, leaf in flat:
        keys = [str(getattr(p, "key", "")) for p in path]
        if any(k in ("w_gate", "w_up", "w_down") for k in keys):
            routed += int(leaf.size)
    e, k = cfg.moe.num_experts, cfg.moe.top_k
    return float(total - routed * (1.0 - k / e))


def model_flops(cfg, params_tree, shape_cfg) -> float:
    n_act = active_params(cfg, params_tree)
    if shape_cfg.kind == "train":
        tokens = shape_cfg.global_batch * shape_cfg.seq_len
        return 6.0 * n_act * tokens
    if shape_cfg.kind == "prefill":
        tokens = shape_cfg.global_batch * shape_cfg.seq_len
        return 2.0 * n_act * tokens
    # decode: one token per sequence
    return 2.0 * n_act * shape_cfg.global_batch
