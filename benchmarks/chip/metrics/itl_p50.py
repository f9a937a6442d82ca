"""Median gap between consecutive output tokens of a request, both stamped
in the traced span of the window (host clock, ms): the wall time of a step
that only decodes, which a new request waits out before its prefill
runs."""
from benchmarks.chip import stats


def read(rec):
    gaps = rec.get("itl_s")
    if not gaps:
        return None
    return 1e3 * stats.percentile(gaps, 50)
