"""The profiler trace of a run, and its reduction to device busy time,
op counts and idle gaps named by the harness's host spans.

A trace is reduced from a plain structure, so that a small recorded
trace kept with the tests checks the same code::

    {"planes": [{"name": ..., "lines": [{"name": ...,
                 "events": [[name, start_ns, duration_ns], ...]}]}]}
"""
from __future__ import annotations

import glob
import os
import re
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Tuple

from bench.records import TraceSummary

#: where a run's profile is written and read back, inside the checkout
DEFAULT_DIR = Path(__file__).resolve().parent.parent / ".bench_trace"

#: the host span around the measured window
WINDOW_SPAN = "bench.window"
#: the harness's host spans, by what the host is doing inside them
HOST_SPANS = ("engine.run", "backend.run_workload", "client.wait",
              WINDOW_SPAN)
#: a gap covered by no span but the window's is the client loop's
_OUTSIDE = "client.loop"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OPS_LINE = "XLA Ops"
# an op event is named by its HLO text: "%copy.1 = s32[8,32]{1,0:...} copy(..."
_HLO = re.compile(r"^%[\w.\-]+ = (?P<type>.+?) (?P<op>[\w\-]+)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_kind(name: str) -> str:
    """An op event's opcode and result type, without layouts: what the
    breakdown adds up ("copy s32[8,32]", "custom-call:tpu_custom_call
    (s32[8,32], s32[8,32])"); a name that is not HLO text stays as it is."""
    m = _HLO.match(name)
    if not m:
        return name
    op = m.group("op")
    target = _TARGET.search(name)
    if op == "custom-call" and target:
        op = f"{op}:{target.group(1)}"
    typ = m.group("type")
    while _LAYOUT.search(typ):
        typ = _LAYOUT.sub("", typ)
    return f"{op} {typ}"


def start(log_dir: str) -> None:
    """Start the profiler with Python's own tracer off (it would record
    every Python call of the host-bound walk)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def load(log_dir: str) -> Dict:
    """The newest ``.xplane.pb`` under ``log_dir`` as a plain structure."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {log_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    return {"planes": [
        {"name": p.name, "lines": [
            {"name": ln.name,
             "events": [[e.name, e.start_ns, e.duration_ns]
                        for e in ln.events]}
            for ln in p.lines]}
        for p in pd.planes]}


def device_ops(trace: Dict) -> List[List[Tuple[str, float, float]]]:
    """Per chip, its op events as ``(name, start_ns, end_ns)``."""
    chips = []
    for p in trace["planes"]:
        if not _DEVICE_PLANE.match(p["name"]):
            continue
        evs = [(n, s, s + d) for ln in p["lines"] if ln["name"] == _OPS_LINE
               for n, s, d in ln["events"]]
        chips.append(evs)
    return chips


def host_spans(trace: Dict, names: Iterable[str] = HOST_SPANS
               ) -> List[Tuple[str, float, float]]:
    wanted = set(names)
    return [(n, s, s + d) for p in trace["planes"]
            if not p["name"].startswith("/device:")
            for ln in p["lines"] for n, s, d in ln["events"] if n in wanted]


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Overlapping or touching intervals merged, in order."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The parts of ``[lo, hi]`` that no interval of ``busy`` (merged,
    in order) covers."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def name_gap(gap: Tuple[float, float],
             spans: Sequence[Tuple[str, float, float]]) -> str:
    """The innermost host span over the gap's middle: of the spans that
    hold it, the shortest. Outside every span but the window's, the
    host was in the client loop."""
    mid = (gap[0] + gap[1]) / 2
    holding = [(e - s, n) for n, s, e in spans
               if n != WINDOW_SPAN and s <= mid <= e]
    return min(holding)[1] if holding else _OUTSIDE


def summarize(trace: Dict, top: int = 10) -> TraceSummary:
    """Busy time (union of op intervals, averaged over chips), op count,
    the ops that took most time and the longest idle gaps, inside the
    ``bench.window`` span."""
    spans = host_spans(trace)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span in the "
                         f"trace, found {len(windows)}")
    lo, hi = windows[0]
    chips = device_ops(trace)
    if not chips:
        raise ValueError("the trace holds no device plane")
    busy_ns, n_ops = 0.0, 0
    by_name: Dict[str, float] = {}
    all_gaps: List[Tuple[float, float]] = []
    for evs in chips:
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                  if e > lo and s < hi]
        n_ops += len(inside)
        for n, s, e in inside:
            kind = op_kind(n)
            by_name[kind] = by_name.get(kind, 0.0) + (e - s)
        merged = union((s, e) for _, s, e in inside)
        busy_ns += sum(e - s for s, e in merged)
        all_gaps.extend(gaps(merged, lo, hi))
    longest = sorted(all_gaps, key=lambda g: g[1] - g[0], reverse=True)
    ops = sorted(by_name.items(), key=lambda kv: kv[1], reverse=True)
    return TraceSummary(
        window_s=(hi - lo) / 1e9,
        busy_s=busy_ns / len(chips) / 1e9,
        n_ops=n_ops,
        top_ops=[[n, d / 1e9] for n, d in ops[:top]],
        idle_gaps=[[name_gap(g, spans), (g[1] - g[0]) / 1e9]
                   for g in longest[:top]])
