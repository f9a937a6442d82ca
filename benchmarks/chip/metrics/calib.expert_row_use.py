"""Share of the rows folded by the program's grouped expert folds that
were true routed rows: Σ ``rows`` / Σ ``padded_rows`` over the
``calib.expert_fold`` spans in the traced span. The rest are zero rows
that pad each held expert's chunk to the chunk size. A program without
such spans gives nothing to read."""
from benchmarks.chip import program_spans


def read(rec):
    got = program_spans.window(rec)
    if got is None:
        return None
    lo, hi, spans = got
    folds = program_spans.ending_in(spans, "calib.expert_fold", lo, hi)
    padded = sum(s.args.get("padded_rows", 0) for s in folds)
    if not folds or padded <= 0:
        return None
    return 100.0 * sum(s.args.get("rows", 0) for s in folds) / padded
