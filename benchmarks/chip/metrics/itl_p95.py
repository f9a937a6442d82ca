"""95th percentile of every gap between consecutive output tokens of a
request, both stamped in the traced span of the window (host clock, ms;
the profiler is on, as for every per-layer metric). It sits where the few
steps that carry a prefill meet the many that only decode, so it swings
from run to run too widely for an end-to-end bound."""
from benchmarks.chip import stats


def read(rec):
    gaps = rec.get("itl_s")
    if not gaps:
        return None
    return 1e3 * stats.percentile(gaps, 95)
