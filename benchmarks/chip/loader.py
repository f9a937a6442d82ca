"""Find a cell's parts by name: configuration, traffic, limits, metrics,
kernel cost functions and the table of peaks.

Everything one configuration, traffic mix or per-layer metric needs sits in
a file of its own under this directory, named after it:

    configs/<config>.json      sizes, dtypes, source, what was reduced
    traffic/<traffic>.json     a mix's parameters; its ``kind`` names ...
    kinds/<kind>.py            ... the module that makes and runs its requests
    limits/<cell>.json         the limits ``correct`` is held to
    metrics/<metric>.py        ``read(record) -> float | None``
    kernels/<kernel>.py        ``flops(...)`` and ``hbm_bytes(...)``
    peaks.json                 per ``device_kind``, with its source

so a later change adds a configuration, mix, kind of traffic or metric by
adding files and ``BENCHMARK.json`` entries, never by editing one. A metric
that one measure reports per cell (``device_idle.chat``,
``device_idle.calib``) shares the reader of that measure
(``metrics/device_idle.py``): the reader of ``a.b.c`` is the first of
``a.b.c.py``, ``a.b.py`` and ``a.py`` that exists. Every function takes the
benchmark directory as ``root`` (default: this directory), so tests can
point the loader at a copy with files dropped in.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))


def _read_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark(path: Optional[str] = None) -> Dict[str, Any]:
    """``BENCHMARK.json`` at the root of the checkout."""
    return _read_json(path or os.path.join(CHECKOUT, "BENCHMARK.json"))


def cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(have {[w['name'] for w in bench['workloads']]})")


def metrics_for(bench: Dict[str, Any], cell_name: str, section: str
                ) -> List[Dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` metrics that this cell reports:
    those without a ``workloads`` key, and those that list the cell."""
    return [m for m in bench[section]
            if "workloads" not in m or cell_name in m["workloads"]]


def config(name: str, root: str = HERE) -> Dict[str, Any]:
    return _read_json(os.path.join(root, "configs", f"{name}.json"))


def traffic(name: str, root: str = HERE) -> Dict[str, Any]:
    return _read_json(os.path.join(root, "traffic", f"{name}.json"))


def limits(cell_name: str, root: str = HERE) -> Dict[str, Any]:
    return _read_json(os.path.join(root, "limits", f"{cell_name}.json"))


def peaks(device_kind: str, root: str = HERE) -> Dict[str, Any]:
    table = _read_json(os.path.join(root, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(have {sorted(table['devices'])})")
    return table["devices"][device_kind]


def _module(path: str, label: str):
    spec = importlib.util.spec_from_file_location(label, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: str = HERE):
    """The ``read`` function of the metric's reader: ``metrics/<name>.py``,
    or that of the measure it reports for one cell, found by dropping the
    name's last dotted parts."""
    parts = name.split(".")
    for k in range(len(parts), 0, -1):
        stem = ".".join(parts[:k])
        path = os.path.join(root, "metrics", f"{stem}.py")
        if os.path.exists(path):
            return _module(path, f"chipbench_metric_{stem}").read
    raise FileNotFoundError(f"no reader for metric {name!r} under "
                            f"{os.path.join(root, 'metrics')}")


def kind(name: str, root: str = HERE):
    """The module ``kinds/<name>.py`` of a traffic mix's ``kind``: it
    makes the mix's requests from the seed and runs the program under them
    (``run(**kwargs)``, see ``harness.run_cell``)."""
    path = os.path.join(root, "kinds", f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no module for traffic kind {name!r}: "
                                f"{path} does not exist")
    return _module(path, f"chipbench_kind_{name}")


def kernel_cost(name: str, root: str = HERE):
    """The module ``kernels/<name>.py`` (``flops`` and ``hbm_bytes``)."""
    return _module(os.path.join(root, "kernels", f"{name}.py"),
                   f"chipbench_kernel_{name}")
