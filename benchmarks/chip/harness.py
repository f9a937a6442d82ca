"""One run of one cell: set-up, the measured window, the reading of the
trace, and the comparison that decides ``correct``.

``run_cell`` is what ``run.py`` calls once it has found a chip; tests call
it on the CPU at a small size with ``require_chip=False``.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Dict, Optional

from benchmarks.chip import loader

# process start, as near as Python lets us see it: set-up runs from here
T_START = time.perf_counter()


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def require_chip(chips: int) -> None:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")


class Compiles:
    """Compile seconds and persistent-cache hits, from JAX's own events
    (the listener ``chip_smoke.py``'s ``Phases`` uses)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        self.hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.count += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> Dict[str, float]:
        return {"compile_s": self.seconds, "compiles": self.count,
                "cache_hits": self.hits}


def annotator(enabled: bool) -> Callable:
    """``ann(name)``: a ``jax.profiler.TraceAnnotation`` in traced runs, so
    the trace can say what the host did in each idle gap; nothing
    otherwise."""
    if not enabled:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def device_info() -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def enable_compile_cache() -> str:
    """The program's persistent cache (``$JAX_COMPILATION_CACHE_DIR`` or
    ``<checkout>/.jax_cache``), keeping every compile, however short, so
    that a warm run loads every program it runs: eager calibration ops
    each compile in well under JAX's default threshold of one second."""
    import jax
    from repro import compile_cache
    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             bench: Optional[Dict[str, Any]] = None, root: str = loader.HERE,
             require_chip_: bool = True, control: bool = False,
             overrides: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Run one cell once; returns the result object (without printing).

    The traffic mix's ``kind`` names the module (``kinds/<kind>.py``)
    whose ``run`` makes the requests from the seed and runs the program.
    With ``control``, that module puts the control of its comparison in the
    program's place, so that ``correct`` must come out false.

    ``overrides`` replace top-level keys of the configuration (``config``)
    and traffic (``traffic``) files, and limits (``limits``): tests use it
    to run a cell at a size the CPU can hold."""
    bench = bench or loader.benchmark()
    cell = loader.cell(bench, cell_name)
    if require_chip_:
        require_chip(int(cell["chips"]))
    overrides = overrides or {}
    conf = {**loader.config(cell["config"], root),
            **overrides.get("config", {})}
    mix = {**loader.traffic(cell["traffic"], root),
           **overrides.get("traffic", {})}
    lim = {**loader.limits(cell_name, root), **overrides.get("limits", {})}
    compiles = Compiles()
    if require_chip_:
        enable_compile_cache()
    from benchmarks.chip import trace as chiptrace
    tracer = chiptrace.Tracer(trace, float(mix.get("trace_seconds", seconds)))
    ann = annotator(trace)
    kind = loader.kind(mix["kind"], root)
    out = kind.run(conf=conf, mix=mix, seed=seed, seconds=seconds, ann=ann,
                   tracer=tracer, compiles=compiles, control=control,
                   t_start=T_START)
    tracer.finish()
    # out: e2e (name -> value), record (for the per-layer readers), info,
    # attempted, failed, device, compared (name -> value)
    compared = out["compared"]
    correct = bool(compared) and all(
        ok(v, lim[name]) for name, v in compared.items())
    metrics: Dict[str, Dict[str, Any]] = {}
    if trace:
        record = dict(out["record"])
        record.update(cell=cell, conf=conf, mix=mix, trace=tracer.summary)
        record["peaks"] = loader.peaks(out["device"]["kind"], root) \
            if out["device"]["platform"] == "tpu" else None
        for m in loader.metrics_for(bench, cell_name, "per_layer"):
            v = loader.metric_reader(m["name"], root)(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in loader.metrics_for(bench, cell_name, "end_to_end"):
            if m["name"] in out["e2e"]:
                metrics[m["name"]] = {"value": out["e2e"][m["name"]],
                                      "unit": m["unit"]}
    device = dict(out["device"])
    if trace and tracer.summary is not None:
        device["busy_s"] = tracer.summary.busy_s
        device["window_s"] = tracer.summary.window_s
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if trace and tracer.summary is not None:
        result["breakdown"] = tracer.summary.breakdown()
    result["info"] = out.get("info", {})
    # the numbers compared come last, each beside its limit
    result["compared"] = {name: {"value": v, "limit": lim[name]["limit"],
                                 "pass_if": lim[name]["pass_if"]}
                          for name, v in compared.items()}
    return result


def ok(value: float, lim: Dict[str, Any]) -> bool:
    if value != value:                      # NaN never passes
        return False
    if lim["pass_if"] == "le":
        return value <= lim["limit"]
    if lim["pass_if"] == "ge":
        return value >= lim["limit"]
    raise ValueError(f"unknown pass_if {lim['pass_if']!r}")
