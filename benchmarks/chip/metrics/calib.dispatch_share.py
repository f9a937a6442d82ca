"""Share of the traced span that the host spent dispatching calibration:
the time inside the program's ``calib.capture`` spans (one eager capture
forward, with every R-factor fold it triggers), over the traced span. The
rest is the host waiting on the chip for the batch's R factors."""
from benchmarks.chip import program_spans


def read(rec):
    got = program_spans.window(rec)
    if got is None:
        return None
    lo, hi, spans = got
    caps = [s for s in spans if s.name == "calib.capture"]
    if not caps:
        return None
    inside = sum(min(s.end, hi) - max(s.start, lo) for s in caps)
    return 100.0 * inside / (hi - lo)
