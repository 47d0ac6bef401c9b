"""Finds what ``BENCHMARK.json`` names: each configuration, traffic mix,
metric reader, runner kind and kernel is a file of its own under
``bench/``, so a later change adds a cell, a mix, a metric, a runner or
a kernel by adding files.
"""
from __future__ import annotations

import importlib
import json
import pkgutil
import re
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Mapping, Optional

import bench.kernels

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _checked(name: str) -> str:
    if not _NAME.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def load_benchmark(root: Path = ROOT) -> Dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def workload(bench: Mapping, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def _json(kind: str, name: str, base: Path) -> Dict:
    path = base / kind / f"{_checked(name)}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    with open(path) as f:
        return json.load(f)


def load_config(name: str, base: Path = BENCH_DIR) -> Dict:
    """``bench/configs/<name>.json``."""
    return _json("configs", name, base)


def load_traffic(name: str, base: Path = BENCH_DIR) -> Dict:
    """``bench/traffic/<name>.json``."""
    return _json("traffic", name, base)


def runner(kind: str) -> Callable:
    """``bench/runners/<kind>.py``'s ``run``."""
    return importlib.import_module(f"bench.runners.{_checked(kind)}").run


def kernel(name: str) -> ModuleType:
    """``bench/kernels/<name>.py``: its ``program``, ``expected`` and
    ``work`` (see ``bench/kernels/__init__.py``)."""
    module = f"bench.kernels.{_checked(name)}"
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        known = sorted(m.name for m in
                       pkgutil.iter_modules(bench.kernels.__path__))
        raise KeyError(f"no kernel file {name}.py in bench/kernels; "
                       f"known: {known}") from None


def reader(metric: str) -> Callable:
    """``bench/metrics/<metric>.py``'s ``read(run) -> value or None``
    (a metric name's dots become underscores in its file name)."""
    mod = _checked(metric).replace(".", "_").replace("-", "_")
    return importlib.import_module(f"bench.metrics.{mod}").read


def metrics_for(bench: Mapping, cell: str, trace: bool) -> List[Dict]:
    """The metrics a run of ``cell`` reports: the end-to-end ones with
    the profiler off, the per-layer ones with it on; a metric with a
    ``workloads`` list belongs to those cells only."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell in m["workloads"]]


def read_metrics(specs: List[Mapping], run) -> Dict[str, Dict]:
    """Each metric's reader over the run; a reader that finds nothing
    to read returns ``None`` and the metric is left out."""
    out: Dict[str, Dict] = {}
    for m in specs:
        value: Optional[float] = reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
