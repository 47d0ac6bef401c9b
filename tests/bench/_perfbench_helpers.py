"""Shared set-up for the benchmark's tests: the repo root on the path,
and small cells over the configurations in ``data/``."""
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def small_bench():
    """``BENCHMARK.json`` with small cells over ``data/``'s files."""
    from bench import spec
    bench = spec.load_benchmark()
    bench["workloads"] = [
        {"name": "tiny.conv.closed", "config": "tiny-conv",
         "traffic": "closed4", "chips": 1},
        {"name": "tiny.conv.open", "config": "tiny-conv",
         "traffic": "open40", "chips": 1},
        {"name": "tiny.fft.closed", "config": "tiny-fft",
         "traffic": "closed4", "chips": 1}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    return bench


def run_small(cell, seed=2**31 + 11, seconds=0.5, trace=False, **kw):
    """One run of a small cell on the CPU, past the look for a chip."""
    from bench import run as bench_run
    return bench_run.execute(small_bench(), cell, seed, seconds, trace, CPU,
                             time.perf_counter(), base=DATA, **kw)


@pytest.fixture
def no_compile_cache(monkeypatch):
    """Runs in tests leave JAX's persistent cache as the suite set it."""
    from bench.runners import kvi_serve
    monkeypatch.setattr(kvi_serve, "_enable_cache", lambda: None)
