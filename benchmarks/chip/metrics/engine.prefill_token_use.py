"""Share of the batched prefill's token slots that held a prompt token:
the ``tokens`` over the ``padded_tokens`` (batch bucket x suffix-length
bucket) of the program's ``serve.prefill_batch`` spans that end in the
traced span, summed."""
from benchmarks.chip import program_spans


def read(rec):
    got = program_spans.window(rec)
    if got is None:
        return None
    lo, hi, spans = got
    calls = program_spans.ending_in(spans, "serve.prefill_batch", lo, hi)
    padded = sum(s.args["padded_tokens"] for s in calls)
    if padded <= 0:
        return None
    return 100.0 * sum(s.args["tokens"] for s in calls) / padded
