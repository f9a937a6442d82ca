"""Share of the traced span that the host spent routing MoE layers in
calibration: the self time of the program's ``calib.route`` spans (the
router, the dispatch of each held expert's rows and the one read of their
counts, per MoE layer and batch) over the traced span. A program without
such spans gives nothing to read."""
from benchmarks.chip import program_spans


def read(rec):
    got = program_spans.window(rec)
    if got is None:
        return None
    lo, hi, spans = got
    if not any(s.name == "calib.route" for s in spans):
        return None
    return 100.0 * program_spans.self_seconds(
        spans, ("calib.route",), lo, hi) / (hi - lo)
