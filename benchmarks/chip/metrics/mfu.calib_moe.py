"""Model FLOP/s utilisation of MoE calibration at one chip's expert share:
the traced batches' operations over the traced window times the chip's
bf16 peak.

A batch's operations are the capture forward's (``kernels/forward_mla_moe.py``,
each held expert at the rows routed to it) plus one QR fold per distinct
input (``kernels/tsqr_fold.py``): a layer's attention input (read by W_q
and W_kv_a), latent, attention output, MLP input (gate and up) and MLP
hidden state, each of the batch's tokens in ``fold_rows`` chunks, and each
held expert's input and hidden state at its true rows. Zero rows that pad
an expert's chunk are not counted. Calibration runs in float32 at
``"highest"``, several bf16 passes per matmul, so it cannot come near that
peak.
"""
import os

from benchmarks.chip import loader

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(rec):
    tr, peaks = rec.get("trace"), rec.get("peaks")
    counts = rec.get("expert_counts")
    if tr is None or peaks is None or tr.window_s <= 0 or not counts:
        return None
    fwd = loader.kernel_cost("forward_mla_moe", ROOT)
    qr = loader.kernel_cost("tsqr_fold", ROOT)
    m, mix = rec["model"], rec["mix"]
    tokens = rec["tokens_per_batch"]
    dense = qr.batch_flops(rec["fold_widths"], tokens, rec["fold_rows"])
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    ops = 0.0
    for layers in counts:
        ops += fwd.batch_flops(m, int(mix["batch"]), int(mix["seq_len"]),
                               layers) + dense
        ops += sum(qr.fold_flops(d, c) + qr.fold_flops(f, c)
                   for c in (c for held in layers for c in held) if c)
    return 100.0 * ops / (tr.window_s * peaks["bf16_flops_per_s"])
