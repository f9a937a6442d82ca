"""Model FLOP/s utilisation: the model's operations over the traced steps
or batches, over the traced window times the chip's bf16 peak.

- Serving steps (``steps`` in the record): the forward operations of every
  token the traced steps processed, prefill and decode, attention over the
  actual lengths (``kernels/forward.py``).
- Calibration batches (``batches``): the capture forward's operations plus
  every QR fold's (``kernels/forward.py``, ``kernels/tsqr_fold.py``).
  Calibration runs in float32 at ``"highest"``, several bf16 passes per
  matmul, so it cannot come near that peak.
"""
import os

from benchmarks.chip import loader

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(rec):
    tr, peaks = rec.get("trace"), rec.get("peaks")
    if tr is None or peaks is None or tr.window_s <= 0:
        return None
    fwd = loader.kernel_cost("forward", ROOT)
    m = rec["model"]
    if rec.get("steps"):
        ops = sum(fwd.step_flops(s.prefill, s.decode, m)
                  for s in rec["steps"])
    elif rec.get("batches"):
        qr = loader.kernel_cost("tsqr_fold", ROOT)
        mix = rec["mix"]
        per_batch = (fwd.calib_flops(m, int(mix["batch"]),
                                     int(mix["seq_len"]))
                     + qr.batch_flops(rec["widths"].values(),
                                      rec["tokens_per_batch"],
                                      rec["fold_rows"]))
        ops = per_batch * len(rec["batches"])
    else:
        return None
    return 100.0 * ops / (tr.window_s * peaks["bf16_flops_per_s"])
