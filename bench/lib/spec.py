"""Find a cell's data by name: BENCHMARK.json, configuration, traffic mix,
peaks, model family references, per-layer metric readers and kernel cost
functions.

Every piece that belongs to one configuration, one model family, one
traffic mix, one per-layer metric or one kernel is a file of its own,
found here by the name ``BENCHMARK.json`` or the configuration gives it:

* ``bench/configs/<config>.json``   a model configuration, which names
  its family in ``"reference"``;
* ``bench/references/<family>.py``  a family's plain reference: seeded
  scales, weights, forward and operation count (the interface is in
  ``lib/reference.py``);
* ``bench/traffic/<traffic>.json``  a traffic mix;
* ``bench/metrics/<metric>.py``     a per-layer metric reader, ``read(ctx)``;
* ``bench/kernels/<kernel>.py``     a kernel's operation and byte count.

Adding one of these is adding a file and an entry; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return _json(ROOT / "BENCHMARK.json")


def cell(name: str) -> Dict[str, Any]:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> Dict[str, Any]:
    return _json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> Dict[str, Any]:
    return _json(BENCH / "traffic" / f"{name}.json")


def peaks(device_kind: str) -> Dict[str, Any]:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    table = _json(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json")
    return table["devices"][device_kind]


def _module(path: Path, label: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(label, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(name: str) -> ModuleType:
    """The family file that a configuration's ``"reference"`` names."""
    return _module(BENCH / "references" / f"{name}.py",
                   "bench_reference_" + name.replace(".", "_"))


def metric_reader(name: str) -> ModuleType:
    return _module(BENCH / "metrics" / f"{name}.py",
                   "bench_metric_" + name.replace(".", "_"))


def kernel_cost(name: str) -> ModuleType:
    return _module(BENCH / "kernels" / f"{name}.py",
                   "bench_kernel_" + name.replace(".", "_"))


def metrics_of(cell_name: str, section: str):
    """The ``end_to_end`` or ``per_layer`` entries a cell reports."""
    out = []
    for m in benchmark()[section]:
        wl = m.get("workloads")
        if wl is None or cell_name in wl:
            out.append(m)
    return out
