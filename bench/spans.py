"""The program's own host spans in a profiler trace, and the device's
idle time named by them.

The served path marks its layer boundaries with ``kvi.*`` host spans
(``repro/kvi/obs/host.py`` in the program): ``kvi.engine.run`` and its
steps, ``kvi.backend.run_workload``, one ``kvi.walk`` per batched walk
and its parts (``kvi.walk.build``, ``.stage``, ``.call``, ``.sync``,
``.outputs``). They share the device trace's clock, so each idle gap of
the device can be named by the innermost span, the harness's
(``bench/tracing.py``) or the program's, over its middle.
:func:`summarize` reduces a trace (the plain structure of
:func:`bench.tracing.load`) to

- ``program_spans``: per span name, ``[count, seconds]`` clipped to the
  ``bench.window`` span;
- ``idle_by_span``: the device-idle seconds of every gap, summed by the
  span that names it;
- ``idle_gaps``: the longest gaps, ``[span, seconds]``, named as above;

and :func:`readings` gives the walk's and the engine's split from
``program_spans``. A trace of a program without these spans reduces to
empty span totals and no readings.
"""
from __future__ import annotations

import bisect
import heapq
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from bench import tracing

# the program's span names, as text: the benchmark also reads traces of
# programs that have no such spans, and imports nothing of them
PREFIX = "kvi."
ENGINE_RUN = "kvi.engine.run"
RUN_WORKLOAD = "kvi.backend.run_workload"
WALK = "kvi.walk"
WALK_SYNC = "kvi.walk.sync"
#: the walk's one dispatch of its compiled device program
WALK_CALL = "kvi.walk.call"

Span = Tuple[str, float, float]


def program_spans(trace: Dict) -> List[Span]:
    """Every host-plane event whose name starts with ``kvi.``, as
    ``(name, start_ns, end_ns)``."""
    return [(n, s, s + d) for p in trace["planes"]
            if not p["name"].startswith("/device:")
            for ln in p["lines"] for n, s, d in ln["events"]
            if n.startswith(PREFIX)]


def innermost(spans: Sequence[Span]) -> Callable[[float], Optional[str]]:
    """A lookup from a time to the shortest span that holds it (ends
    included; equal lengths go to the smaller name), or ``None`` where
    none does: :func:`bench.tracing.name_gap`'s rule, answered by
    bisection over the spans' boundaries, so that naming every gap of a
    long window stays cheap."""
    bounds = sorted({t for _, s, e in spans for t in (s, e)})
    starts = sorted(spans, key=lambda sp: sp[1])
    at: List[Optional[str]] = []       # the answer at bounds[k] itself
    after: List[Optional[str]] = []    # ... strictly inside (k, k + 1)
    heap: List[Tuple[float, str, float]] = []
    j = 0
    for b in bounds:
        while j < len(starts) and starts[j][1] <= b:
            n, s, e = starts[j]
            heapq.heappush(heap, (e - s, n, e))
            j += 1
        while heap and heap[0][2] < b:
            heapq.heappop(heap)
        at.append(heap[0][1] if heap else None)
        while heap and heap[0][2] <= b:
            heapq.heappop(heap)
        after.append(heap[0][1] if heap else None)

    def name(t: float) -> Optional[str]:
        k = bisect.bisect_right(bounds, t) - 1
        if k < 0:
            return None
        return at[k] if bounds[k] == t else after[k]
    return name


def summarize(trace: Dict, top: int = 10) -> Dict[str, object]:
    """``program_spans``, ``idle_by_span`` and the ``top`` longest
    ``idle_gaps`` of the trace's window (see the module's docstring).
    Gaps are those of :func:`bench.tracing.summarize`: per chip, the
    parts of the window no op event covers."""
    harness = tracing.host_spans(trace)
    windows = [(s, e) for n, s, e in harness if n == tracing.WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {tracing.WINDOW_SPAN!r} span in "
                         f"the trace, found {len(windows)}")
    lo, hi = windows[0]
    spans = program_spans(trace)
    totals: Dict[str, List[float]] = {}
    for n, s, e in spans:
        if e > lo and s < hi:
            t = totals.setdefault(n, [0, 0.0])
            t[0] += 1
            t[1] += (min(e, hi) - max(s, lo)) / 1e9
    name = innermost([sp for sp in harness + spans
                      if sp[0] != tracing.WINDOW_SPAN])
    idle: Dict[str, float] = {}
    named = []
    for evs in tracing.device_ops(trace):
        busy = tracing.union((max(s, lo), min(e, hi)) for _, s, e in evs
                             if e > lo and s < hi)
        for g0, g1 in tracing.gaps(busy, lo, hi):
            n = name((g0 + g1) / 2) or tracing._OUTSIDE
            idle[n] = idle.get(n, 0.0) + (g1 - g0) / 1e9
            named.append([n, (g1 - g0) / 1e9])
    named.sort(key=lambda g: g[1], reverse=True)
    return {"program_spans": totals, "idle_by_span": idle,
            "idle_gaps": named[:top]}


def readings(totals: Dict[str, Sequence[float]]) -> Dict[str, float]:
    """From ``program_spans``: per walk, its milliseconds in host syncs
    (``walk_sync_ms_per_batch``), in dispatching its device program
    (``walk_dispatch_ms_per_batch``) and in neither
    (``walk_self_ms_per_batch``: staging, output copies, a build); per
    engine run, its milliseconds outside the backend
    (``engine_self_ms_per_step``). A reading whose parent span is not
    in the trace is left out."""
    def s(name: str) -> float:
        return totals.get(name, (0, 0.0))[1]
    out: Dict[str, float] = {}
    walks = totals.get(WALK, (0, 0.0))[0]
    if walks:
        sync = s(WALK_SYNC)
        dispatch = s(WALK_CALL)
        out["walk_sync_ms_per_batch"] = 1e3 * sync / walks
        out["walk_dispatch_ms_per_batch"] = 1e3 * dispatch / walks
        out["walk_self_ms_per_batch"] = \
            1e3 * (s(WALK) - sync - dispatch) / walks
    runs = totals.get(ENGINE_RUN, (0, 0.0))[0]
    if runs:
        out["engine_self_ms_per_step"] = \
            1e3 * (s(ENGINE_RUN) - s(RUN_WORKLOAD)) / runs
    return out
