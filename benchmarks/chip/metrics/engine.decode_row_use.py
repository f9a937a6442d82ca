"""Share of the decode batch's rows that carried a request: the ``rows``
over the ``padded_rows`` (the batch bucket) of the program's
``serve.decode_step`` spans that end in the traced span, summed."""
from benchmarks.chip import program_spans


def read(rec):
    got = program_spans.window(rec)
    if got is None:
        return None
    lo, hi, spans = got
    steps = program_spans.ending_in(spans, "serve.decode_step", lo, hi)
    padded = sum(s.args["padded_rows"] for s in steps)
    if padded <= 0:
        return None
    return 100.0 * sum(s.args["rows"] for s in steps) / padded
