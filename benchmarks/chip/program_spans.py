"""The program's own spans over the traced span of a window, for the
per-layer readers that read them.

While a ``jax.profiler`` session runs, every ``repro.obs.trace`` span is
recorded on ``time.perf_counter`` seconds, the clock of the record's
``host_window``, and ``trace.spans(lo, hi)`` returns the ones that overlap
it. A program without ``trace.spans`` records no such spans: the readers
then read nothing, and say so with ``None``.
"""
from __future__ import annotations

from typing import Iterable, List, Optional, Tuple


def window(rec) -> Optional[Tuple[float, float, List]]:
    """``(lo, hi, spans)`` of the traced span; ``None`` where the program
    records no spans, or the window cannot be read whole."""
    lo, hi = rec.get("host_window") or (None, None)
    if lo is None or hi is None or hi <= lo:
        return None
    from repro.obs import trace
    read = getattr(trace, "spans", None)
    if read is None:
        return None
    spans = read(lo, hi)
    return None if spans is None else (lo, hi, spans)


def ending_in(spans: Iterable, name: str, lo: float, hi: float) -> List:
    """The spans named ``name`` that end inside ``[lo, hi]``."""
    return [s for s in spans if s.name == name and lo <= s.end <= hi]


def _inside(s, lo: float, hi: float) -> float:
    return max(0.0, min(s.end, hi) - max(s.start, lo))


def self_seconds(spans: List, names: Iterable[str], lo: float,
                 hi: float) -> float:
    """Summed time inside ``[lo, hi]`` of the spans named in ``names``,
    less that of the spans directly inside them (their self time)."""
    names = set(names)
    mine = {s.id: s for s in spans if s.name in names}
    total = sum(_inside(s, lo, hi) for s in mine.values())
    total -= sum(_inside(s, lo, hi) for s in spans if s.parent in mine)
    return total
