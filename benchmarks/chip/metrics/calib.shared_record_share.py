"""Share of the calibration records in the traced span that took the R
factor the record before them folded from the same input and the same
stream state, instead of folding it again: the program's ``calib.record``
spans with ``shared`` true, over every ``calib.record`` span there. A
program whose records carry no ``shared`` arg gives nothing to read."""
from benchmarks.chip import program_spans


def read(rec):
    got = program_spans.window(rec)
    if got is None:
        return None
    _, _, spans = got
    recs = [s for s in spans if s.name == "calib.record"]
    if not recs or not all("shared" in s.args for s in recs):
        return None
    return 100.0 * sum(bool(s.args["shared"]) for s in recs) / len(recs)
