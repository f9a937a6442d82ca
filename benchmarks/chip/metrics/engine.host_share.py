"""Share of the traced span that the engine's step spent in host work of
its own: the self time of the program's ``serve.admit``, ``serve.prepare``,
``serve.sample`` and ``serve.emit`` spans (less the spans inside them),
over the traced span. The rest of a step is its prefill and decode calls,
each dispatched and waited on (``serve.prefill_batch``,
``serve.decode_step``)."""
from benchmarks.chip import program_spans

HOST = ("serve.admit", "serve.prepare", "serve.sample", "serve.emit")


def read(rec):
    got = program_spans.window(rec)
    if got is None:
        return None
    lo, hi, spans = got
    if not any(s.name in HOST for s in spans):
        return None
    return 100.0 * program_spans.self_seconds(spans, HOST, lo, hi) / (hi - lo)
