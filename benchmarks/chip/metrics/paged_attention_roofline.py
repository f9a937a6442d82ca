"""Roofline share of the paged decode-attention kernel: the least time the
chip could take for the traced steps' decode attention (the larger of
operations over peak FLOP/s and bytes over peak bandwidth, from
``kernels/paged_attention.py`` over the context lengths the harness noted
for each decoded token, once per layer) over the summed device time of the
kernel's events (``paged_attention``) in the trace."""
import os

from benchmarks.chip import loader

KERNEL = "paged_attention"


def read(rec):
    steps, tr, peaks = rec.get("steps"), rec.get("trace"), rec.get("peaks")
    if not steps or tr is None or peaks is None:
        return None
    spent = tr.op_seconds.get(KERNEL, 0.0)
    if spent <= 0:
        return None
    cost = loader.kernel_cost(KERNEL, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    m = rec["model"]
    least = sum(cost.least_seconds(s.decode, m, peaks)[0]
                for s in steps if s.decode) * m["n_layers"]
    return 100.0 * least / spent
