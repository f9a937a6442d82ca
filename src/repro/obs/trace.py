"""Span tracer: Chrome/Perfetto ``trace_event`` JSON, spans on the
``jax.profiler`` clock, and windows of spans for in-process readers.

One process-wide tracer records *complete* events (``ph: "X"``) around the
serving and calibration hot paths — scheduler admission, batched prefill,
decode steps, sampling, token emission, preemption, copy-on-write page
copies, checkpoint I/O, calibration capture and R-factor folds,
live-traffic recalibration (``serve.recalib_capture/solve/check/swap``) —
plus *instant* events (``ph: "i"``) for jit compiles, prefix-cache
evictions and rejected recalibration solves. The output loads directly in
``chrome://tracing`` / https://ui.perfetto.dev.

Design constraints (docs/observability.md has the span taxonomy):

  * **Follows the profiler.** While a ``jax.profiler`` session is active,
    every ``span(name, **args)`` also enters a
    ``jax.profiler.TraceAnnotation(name, **args)``, so the span lands on
    the profiler's host plane on the device trace's clock, and is recorded
    here too: in the enabled tracer, else in a ring of ``RING_EVENTS``
    installed for it. ``spans(lo, hi)`` hands the recorded spans that
    overlap ``[lo, hi]`` (``time.perf_counter`` seconds) to in-process
    readers, each with its parent on the same thread and its args.
  * **Near-zero overhead when off.** With no profiler session and no
    ``enable()``, ``span()`` reads one global and calls
    ``TraceAnnotation.is_enabled()``, then returns a shared no-op context
    manager: no allocation, no clock read. Args are passed raw and only
    formatted when an event is exported.
  * **Thread-safe.** Spans carry the recording thread's id (checkpointing
    writes on a background thread) and events are appended under a lock;
    per-thread spans nest strictly because they come from ``with`` blocks
    on that thread.
  * **Bounded memory on demand.** ``enable(max_events=N)`` turns the event
    list into a ring (``deque(maxlen=N)``): long-running serving keeps the
    most recent N events and counts the rest in ``tracer.dropped``
    (``launch/serve.py --trace-max-events`` wires this). A window that
    reaches back past a dropped event cannot be read: ``spans`` returns
    ``None``.

Usage (the launchers wire ``--trace-out`` to this):

    from repro.obs import trace
    trace.enable()
    with trace.span("serve.decode_step", rows=4) as sp:
        ...
        sp.set(finished=1)              # args known only at the end
    trace.complete("serve.queue_wait", req.arrival_time, req_id=7)
    trace.instant("serve.decode_compile", sig=(4, 8, True))
    trace.save("trace.json")
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

# events the ring installed for a profiler session keeps
RING_EVENTS = 65536

_profiling = TraceAnnotation.is_enabled
_now = time.perf_counter
_thread_id = threading.get_ident

# an event as recorded: (ph, name, start_s, end_s, tid, args, id, parent),
# ``ph`` "X" for a span, "a" for an interval from ``complete()``, "i" for an
# instant, "M" for metadata; ``id``/``parent`` are set on the first two
_PH, _NAME, _START, _END, _TID, _ARGS, _ID, _PARENT = range(8)


class Span(NamedTuple):
    """One recorded complete event, as ``spans()`` returns it. ``start`` and
    ``end`` are ``time.perf_counter`` seconds; ``parent`` is the ``id`` of
    the span that enclosed it on the same thread, or ``None``."""
    name: str
    start: float
    end: float
    id: int
    parent: Optional[int]
    args: Dict[str, Any]
    tid: int


class _NullSpan:
    """Shared do-nothing context manager returned while tracing is off."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **args) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    """One recording ``with`` block: timestamps at enter, emits at exit;
    also a profiler annotation when a profiler session was on at creation."""

    __slots__ = ("_tracer", "_name", "_args", "_ann", "_start", "_id",
                 "_parent", "_stack", "_tid")

    def __init__(self, tracer: "Tracer", name: str, args: Dict[str, Any],
                 profiled: bool):
        self._tracer = tracer
        self._name = name
        self._args = args
        self._ann = TraceAnnotation(name, **args) if profiled else None

    def set(self, **args) -> None:
        """Add args known only once the work is done."""
        self._args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __enter__(self) -> "_Span":
        t = self._tracer
        self._tid = tid = _thread_id()
        stack = t._stacks.get(tid)
        if stack is None:
            stack = t._stacks[tid] = []
        self._parent = stack[-1] if stack else None
        self._id = next(t._ids)
        stack.append(self._id)
        self._stack = stack
        if self._ann is not None:
            self._ann.__enter__()
        self._start = _now()
        return self

    def __exit__(self, *exc) -> bool:
        end = _now()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._stack.pop()
        self._tracer._emit(("X", self._name, self._start, end, self._tid,
                            self._args, self._id, self._parent))
        return False


def _json_args(args: Dict[str, Any]) -> Dict[str, Any]:
    """Args as the JSON export writes them: scalars as they are, anything
    else (tuples, arrays) as its ``str``."""
    return {k: v if v is None or isinstance(v, (bool, int, float, str))
            else str(v) for k, v in args.items()}


class Tracer:
    """Collects trace events; ``save()`` writes Perfetto-loadable JSON."""

    def __init__(self, max_events: Optional[int] = None):
        self._lock = threading.Lock()
        # deque(maxlen=None) == unbounded append; a positive cap makes it a
        # ring holding the most recent events (bounded-memory serving)
        self._events: deque = deque(maxlen=max_events)
        self._t0 = _now()
        self._pid = os.getpid()
        self._ids = itertools.count()
        self._stacks: Dict[int, List[int]] = {}     # tid -> open span ids
        self.dropped = 0
        # latest end of a dropped event: windows before it are incomplete
        self._dropped_end = float("-inf")

    # ------------------------------------------------------------- recording
    def _emit(self, event: tuple) -> None:
        with self._lock:
            evs = self._events
            if evs.maxlen is not None and len(evs) == evs.maxlen:
                self.dropped += 1
                self._dropped_end = max(self._dropped_end, evs[0][_END])
            evs.append(event)

    @property
    def max_events(self) -> Optional[int]:
        return self._events.maxlen

    def set_max_events(self, max_events: Optional[int]) -> None:
        """Re-cap the ring in place, keeping the newest events."""
        with self._lock:
            if max_events == self._events.maxlen:
                return
            old = list(self._events)
            if max_events is not None and len(old) > max_events:
                gone = old[:len(old) - max_events]
                self.dropped += len(gone)
                self._dropped_end = max(self._dropped_end,
                                        *(e[_END] for e in gone))
                old = old[len(gone):]
            self._events = deque(old, maxlen=max_events)

    def span(self, name: str, **args) -> _Span:
        return _Span(self, name, args, _profiling())

    def complete(self, name: str, start: float, end: Optional[float] = None,
                 **args) -> None:
        """A complete event whose start was stamped earlier (a request's
        arrival, say); it ends now unless ``end`` is given."""
        self._emit(("a", name, start, _now() if end is None else end,
                    _thread_id(), args, next(self._ids), None))

    def instant(self, name: str, **args) -> None:
        now = _now()
        self._emit(("i", name, now, now, _thread_id(), args, None, None))

    def name_thread(self, name: str) -> None:
        """Label the calling thread's lane in the trace viewer (``M``
        metadata event) — background workers call this once at start so
        their spans render on a named track."""
        self._emit(("M", "thread_name", self._t0, self._t0, _thread_id(),
                    {"name": name}, None, None))

    # --------------------------------------------------------------- output
    def _chrome(self, e: tuple) -> List[dict]:
        """One recorded event as Chrome ``trace_event`` dicts: an interval
        from ``complete()``, which may overlap others on its thread, as a
        nestable async begin/end pair."""
        ph, tid = e[_PH], e[_TID]
        if ph == "M":
            return [{"name": e[_NAME], "ph": "M", "pid": self._pid,
                     "tid": tid, "args": dict(e[_ARGS])}]
        out = {"name": e[_NAME], "ph": ph,
               "ts": (e[_START] - self._t0) * 1e6, "pid": self._pid,
               "tid": tid}
        if e[_ARGS]:
            out["args"] = _json_args(e[_ARGS])
        if ph == "X":
            out["dur"] = (e[_END] - e[_START]) * 1e6
        elif ph == "i":
            out["s"] = "t"
        else:
            out.update(ph="b", cat="interval", id=e[_ID])
            return [out, {"name": e[_NAME], "ph": "e", "cat": "interval",
                          "id": e[_ID], "ts": (e[_END] - self._t0) * 1e6,
                          "pid": self._pid, "tid": tid}]
        return [out]

    def events(self) -> List[dict]:
        """Every held event as Chrome ``trace_event`` dicts."""
        with self._lock:
            raw = list(self._events)
        return [d for e in raw for d in self._chrome(e)]

    def tail(self, n: int) -> List[dict]:
        """The most recent ``n`` events (postmortem bundles grab this)."""
        with self._lock:
            raw = list(self._events)[-n:] if n > 0 else []
        return [d for e in raw for d in self._chrome(e)]

    def spans(self, lo: float, hi: float) -> Optional[List[Span]]:
        """The spans and intervals overlapping ``[lo, hi]``, in the order
        they ended; ``None`` if the ring dropped an event that could lie
        there."""
        with self._lock:
            if self.dropped and self._dropped_end >= lo:
                return None
            raw = [e for e in self._events
                   if e[_PH] in "Xa" and e[_END] >= lo and e[_START] <= hi]
        return [Span(e[_NAME], e[_START], e[_END], e[_ID], e[_PARENT],
                     e[_ARGS], e[_TID]) for e in raw]

    def save(self, path: str) -> int:
        """Write ``{"traceEvents": [...]}`` JSON; returns the event count."""
        events = self.events()
        doc = {"traceEvents": [
            {"name": "process_name", "ph": "M", "pid": self._pid, "tid": 0,
             "args": {"name": "repro"}},
            *events,
        ], "displayTimeUnit": "ms"}
        with open(path, "w") as f:
            json.dump(doc, f)
        return len(events)


# --------------------------------------------------------------------------
# Module-level singleton: call sites never thread a tracer object around.
# --------------------------------------------------------------------------

_TRACER: Optional[Tracer] = None     # enable()d: records whenever it is set
_RING: Optional[Tracer] = None       # records only while a profiler runs


def enable(max_events: Optional[int] = None) -> Tracer:
    """Install (or return) the process tracer; spans record from now on.

    ``max_events`` caps the in-memory event list as a ring of the most
    recent events (``None`` = unbounded, the default). Re-enabling an
    existing tracer with an explicit cap re-caps it in place.
    """
    global _TRACER
    if _TRACER is None:
        _TRACER = Tracer(max_events=max_events)
    elif max_events is not None:
        _TRACER.set_max_events(max_events)
    return _TRACER


def disable() -> None:
    """Drop the tracer and the profiler's ring; ``span()``/``instant()``
    become no-ops again while no profiler session runs."""
    global _TRACER, _RING
    _TRACER = None
    _RING = None


def enabled() -> bool:
    return _TRACER is not None


def current() -> Optional[Tracer]:
    return _TRACER


def _recorder() -> Tracer:
    """Where a span recorded under the profiler goes: the enabled tracer,
    else the ring, installed on first use."""
    global _RING
    if _TRACER is not None:
        return _TRACER
    if _RING is None:
        _RING = Tracer(max_events=RING_EVENTS)
    return _RING


def span(name: str, **args):
    """Context manager timing ``name``; free no-op when tracing is off."""
    if _profiling():
        return _Span(_recorder(), name, args, True)
    t = _TRACER
    return _NULL_SPAN if t is None else _Span(t, name, args, False)


def complete(name: str, start: float, end: Optional[float] = None,
             **args) -> None:
    """Record ``[start, end or now]`` (``time.perf_counter`` seconds) as a
    complete event; no-op when off. The profiler cannot take an interval
    that began in the past, so it reaches ``spans()`` and the JSON only."""
    if _profiling():
        _recorder().complete(name, start, end, **args)
    elif _TRACER is not None:
        _TRACER.complete(name, start, end, **args)


def instant(name: str, **args) -> None:
    """Point-in-time marker (compiles, evictions) for the enabled tracer's
    JSON; no-op when off."""
    t = _TRACER
    if t is not None:
        t.instant(name, **args)


def name_thread(name: str) -> None:
    """Label the calling thread's trace lane; no-op when off."""
    t = _TRACER
    if t is not None:
        t.name_thread(name)


def spans(lo: float, hi: float) -> Optional[List[Span]]:
    """The recorded spans overlapping ``[lo, hi]`` (``time.perf_counter``
    seconds, the clock of ``Request.arrival_time``); ``[]`` if nothing was
    ever recorded, ``None`` if the ring dropped part of the window."""
    t = _TRACER if _TRACER is not None else _RING
    return [] if t is None else t.spans(lo, hi)


def save(path: str) -> int:
    """Write the active tracer's events to ``path``; 0 when tracing is off."""
    t = _TRACER
    return t.save(path) if t is not None else 0
