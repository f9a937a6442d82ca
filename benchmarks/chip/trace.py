"""Reduction of a profiler trace to device busy time, kernel times and
idle gaps attributed to what the host was doing.

A traced run starts ``jax.profiler`` when the window opens and stops it
once ``trace_seconds`` have passed, after a whole step; once the window has
closed, the ``.xplane.pb`` is read back through ``jax.profiler.ProfileData``
and deleted. From it:

- the traced window: from the start of the first harness annotation
  (``engine.step``, ``harness.*``, ``calib.batch``) to the end of the last,
  on the trace's own clock;
- busy: the union of the intervals of every operation on the device's
  ``XLA Ops`` line (loops and calls left out: their bodies are listed),
  clipped to the window, averaged over the chips used;
- per-operation device seconds, under the HLO instruction's name without
  its numbering (``stable_name``): a Pallas kernel appears under the name
  of the function that calls it, ``paged_attention`` or
  ``chunked_prefill``;
- idle gaps: the stretches of the window in which no operation ran, each
  labelled by the innermost harness annotation that covered most of it.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional, Sequence, Tuple

HARNESS_SPANS = ("engine.step", "harness.submit", "harness.bookkeeping",
                 "harness.wait", "calib.batch")
OPS_LINE = "XLA Ops"
# control flow that holds other operations: its own interval covers its
# body's, which the trace lists as operations of their own
CONTAINERS = ("while", "conditional", "call")
Interval = Tuple[int, int]


def stable_name(event: str) -> str:
    """``%paged_attention.11 = bf16[...] custom-call(...)`` ->
    ``paged_attention``: the instruction's name without its HLO text or
    the numbering XLA gives each copy of an operation."""
    name = event.split(" = ", 1)[0].lstrip("%")
    while True:
        head, dot, tail = name.rpartition(".")
        if not dot or not tail.isdigit():
            return name
        name = head


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The parts of [lo, hi] that merged intervals ``busy`` leave free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap: Interval, spans: Sequence[Tuple[str, int, int]]) -> str:
    """The harness span covering most of ``gap``; the innermost (shortest)
    of equal covers. ``"none"`` where no span covers it."""
    best, best_cover, best_len = "none", 0, None
    for name, s, e in spans:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover <= 0:
            continue
        if cover > best_cover or (cover == best_cover
                                  and (best_len is None or e - s < best_len)):
            best, best_cover, best_len = name, cover, e - s
    return best


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    op_seconds: Dict[str, float]            # device seconds by op name
    idle: List[Tuple[str, float]]           # (label, seconds) per gap
    chips: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> Dict[str, List[List]]:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:10]
        by_label: Dict[str, float] = collections.defaultdict(float)
        for name, s in self.idle:
            by_label[name] += s
        idle = sorted(by_label.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def reduce(path: str, chips: int) -> Summary:
    """Read one ``.xplane.pb``."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    spans: List[Tuple[str, int, int]] = []
    device_ops: List[List[Tuple[str, int, int]]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") or \
                plane.name.startswith("/device:CPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = []
                    for e in line.events:
                        name = stable_name(e.name)
                        if name not in CONTAINERS:
                            ops.append((name, int(e.start_ns),
                                        int(e.start_ns + e.duration_ns)))
                    device_ops.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HARNESS_SPANS:
                        spans.append((e.name, int(e.start_ns),
                                      int(e.start_ns + e.duration_ns)))
    device_ops = [d for d in device_ops if d][:chips]
    if not spans or not device_ops:
        raise ValueError(f"trace {path} holds no harness spans or no "
                         "device operations")
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    busy_ns, op_ns, idle = 0, collections.defaultdict(int), []
    for k, ops in enumerate(device_ops):
        merged = union(clip([(s, e) for _, s, e in ops], lo, hi))
        busy_ns += sum(e - s for s, e in merged)
        for name, s, e in ops:
            if e > lo and s < hi:
                op_ns[name] += min(e, hi) - max(s, lo)
        if k == 0:
            idle = [(label(g, spans), (g[1] - g[0]) * 1e-9)
                    for g in gaps(merged, lo, hi)]
    n = len(device_ops)
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy_ns * 1e-9 / n,
                   op_seconds={k: v * 1e-9 / n for k, v in op_ns.items()},
                   idle=idle, chips=n)


class Tracer:
    """Starts the profiler when the window opens and stops it after whole
    steps once ``seconds`` have passed; inert when ``enabled`` is false.
    ``first_step``/``last_step`` index the steps (or batches) it covered."""

    def __init__(self, enabled: bool, seconds: float, chips: int = 1):
        self.enabled = enabled
        self.seconds = seconds
        self.chips = chips
        self.dir: Optional[str] = None
        self.active = False
        self.first_step = 0
        self.last_step: Optional[int] = None
        self.t_start: Optional[float] = None
        self.t_stop: Optional[float] = None
        self.summary: Optional[Summary] = None

    def start(self, steps: Sequence) -> None:
        self.first_step = len(steps)
        self.t_start = time.perf_counter()
        if not self.enabled:
            return
        import jax
        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        # no Python function tracing: it would record every call of the
        # host loop and slow it; the harness spans are what the gaps need
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.active = True

    def maybe_stop(self, steps: Sequence, force: bool = False) -> None:
        if self.last_step is not None:
            return
        if not force and time.perf_counter() - self.t_start < self.seconds:
            return
        self.last_step = len(steps)
        self.t_stop = time.perf_counter()
        if not self.active:
            return
        import jax
        jax.profiler.stop_trace()
        self.active = False
        print(f"trace: profiler stopped in "
              f"{time.perf_counter() - self.t_stop:.1f} s", file=sys.stderr)

    def finish(self) -> None:
        """Read the trace back, once the window has closed."""
        if self.dir is None:
            return
        t0 = time.perf_counter()
        try:
            found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not found:
                raise FileNotFoundError(f"no .xplane.pb under {self.dir}")
            self.summary = reduce(found[0], self.chips)
        except ValueError as e:             # e.g. a CPU run: no device line
            print(f"trace: {e}", file=sys.stderr)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None
        print(f"trace: read in {time.perf_counter() - t0:.1f} s",
              file=sys.stderr)
