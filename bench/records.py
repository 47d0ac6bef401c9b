"""What one run leaves for the metric readers: the requests of the
window, the backend's batches, the engine's steps, and the summary of
the profiler trace. Runner kinds fill it; ``bench/metrics/*.py`` read
it. Times are seconds on the run's host clock (``time.perf_counter``).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional


@dataclass
class Request:
    due: float                   # open loop: its due time; closed: sent
    sent: float                  # handed to the engine
    done: Optional[float] = None  # its outputs on the host; None: never
    late: Optional[float] = None  # open loop: how late the send was
    ops: int = 0                 # algorithmic work (bench/work.py)
    nbytes: int = 0


@dataclass
class Batch:
    start: float
    end: float
    size: int


@dataclass
class Step:
    start: float                 # around ServeEngine.run
    end: float
    backend_s: float             # of which inside run_workload
    buckets: List[int] = field(default_factory=list)


@dataclass
class TraceSummary:
    window_s: float              # the traced window
    busy_s: float                # union of device op intervals, per chip
    n_ops: int                   # device op events in the window
    top_ops: List[list] = field(default_factory=list)   # [name, s]
    idle_gaps: List[list] = field(default_factory=list)  # [span, s]


@dataclass
class RunRecord:
    open_loop: bool
    setup_s: float
    t_open: float
    requests: List[Request] = field(default_factory=list)
    batches: List[Batch] = field(default_factory=list)
    steps: List[Step] = field(default_factory=list)
    compiles_in_window: int = 0
    setup_phases: Dict[str, Dict[str, float]] = field(default_factory=dict)
    trace: Optional[TraceSummary] = None
    peak: Optional[object] = None      # bench.peaks.Peak of the device
    checks: Dict[str, Dict] = field(default_factory=dict)
    correct: bool = False
    device: Dict[str, object] = field(default_factory=dict)

    @property
    def latencies_s(self) -> List[float]:
        return [r.done - r.due for r in self.requests if r.done is not None]

    @property
    def failed(self) -> int:
        return sum(1 for r in self.requests if r.done is None) + \
            int(self.checks.get("mismatched", {}).get("value", 0))
