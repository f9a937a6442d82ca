"""Least work of one call of the paged decode-attention kernel
(``kernels/paged_attention.py`` of the program): one query token per row,
attending over that row's ``L`` cached keys.

Operations: QKᵀ and PV, two multiply-adds per query head, key position and
head dimension: ``4 * heads * head_dim * L`` per row. Bytes: every cached
key and value of the row read once (``2 * kv_heads * head_dim * L`` at the
cache's width), the query read and the output written once. Padding rows
and page rounding are not work the algorithm needs, and are not counted.
"""
from typing import Dict, Sequence


def flops(lengths: Sequence[int], m: Dict) -> float:
    return float(sum(4 * m["n_heads"] * m["head_dim"] * n for n in lengths))


def hbm_bytes(lengths: Sequence[int], m: Dict, cache_bytes: int = 2,
              act_bytes: int = 2) -> float:
    kv = sum(2 * m["n_kv_heads"] * m["head_dim"] * n for n in lengths)
    qo = 2 * len(lengths) * m["n_heads"] * m["head_dim"]
    return float(kv * cache_bytes + qo * act_bytes)


def least_seconds(lengths: Sequence[int], m: Dict, peaks: Dict):
    """(seconds, bound): the larger of operations over peak FLOP/s and
    bytes over peak bandwidth, and which of the two it is."""
    t_ops = flops(lengths, m) / peaks["bf16_flops_per_s"]
    t_mem = hbm_bytes(lengths, m) / peaks["hbm_bytes_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
