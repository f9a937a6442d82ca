"""90th percentile of the time a request waited for admission (ms): the
durations of the program's ``serve.queue_wait`` events (arrival at
``ContinuousEngine.submit`` to admission by the scheduler) that end in the
traced span."""
from benchmarks.chip import program_spans, stats


def read(rec):
    got = program_spans.window(rec)
    if got is None:
        return None
    lo, hi, spans = got
    waits = [s.end - s.start
             for s in program_spans.ending_in(spans, "serve.queue_wait",
                                              lo, hi)]
    if not waits:
        return None
    return 1e3 * stats.percentile(waits, 90)
