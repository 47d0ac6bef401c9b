"""The reduction from a profiler trace to busy time, op counts and idle
gaps named by the harness's host spans."""
import json

import pytest

from _perfbench_helpers import DATA
from bench import tracing


def _trace(device_events, host_events, chips=1):
    planes = [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": host_events}]}]
    for c in range(chips):
        planes.append({"name": f"/device:TPU:{c}", "lines": [
            {"name": "XLA Modules", "events": [["jit_x", 0, 10**9]]},
            {"name": "XLA Ops", "events": device_events}]})
    planes.append({"name": "/device:TPU:0 SparseCore 0", "lines": [
        {"name": "XLA Ops", "events": [["sc", 0, 10**9]]}]})
    return {"planes": planes}


def test_union_merges_overlapping_and_touching_intervals():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9.5)]) == \
        [(0, 4), (5, 7), (9, 9.5)]
    assert tracing.union([]) == []


def test_gaps_are_the_uncovered_parts_of_the_window():
    busy = [(0, 4), (5, 7), (9, 12)]
    assert tracing.gaps(busy, 1, 10) == [(4, 5), (7, 9)]
    assert tracing.gaps(busy, -2, 13) == [(-2, 0), (4, 5), (7, 9), (12, 13)]
    assert tracing.gaps([], 0, 3) == [(0, 3)]


def test_gap_named_by_innermost_span_over_its_middle():
    spans = [("bench.window", 0, 100), ("engine.run", 10, 60),
             ("backend.run_workload", 20, 40)]
    assert tracing.name_gap((25, 35), spans) == "backend.run_workload"
    assert tracing.name_gap((42, 58), spans) == "engine.run"
    assert tracing.name_gap((70, 90), spans) == "client.loop"
    assert tracing.name_gap((120, 130), spans) == "client.loop"


def test_summary_of_a_hand_made_trace():
    ms = 10**6
    dev = [["copy", 10 * ms, 2 * ms], ["copy", 11 * ms, 2 * ms],
           ["fusion", 30 * ms, 5 * ms], ["outside", 200 * ms, 5 * ms]]
    host = [["bench.window", 0, 100 * ms], ["engine.run", 5 * ms, 50 * ms],
            ["backend.run_workload", 8 * ms, 30 * ms],
            ["PjitFunction(x)", 9 * ms, ms]]
    s = tracing.summarize(_trace(dev, host))
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx(0.008)       # 10..13 and 30..35 ms
    assert s.n_ops == 3                           # the one outside is out
    assert s.top_ops == [["fusion", pytest.approx(0.005)],
                         ["copy", pytest.approx(0.004)]]
    # gaps 35..100 ms (middle in the client loop, after engine.run),
    # 13..30 (inside the backend's span), 0..10 (engine.run from 5 ms)
    assert [g[0] for g in s.idle_gaps] == \
        ["client.loop", "backend.run_workload", "engine.run"]
    assert [g[1] for g in s.idle_gaps] == pytest.approx([0.065, 0.017, 0.010])


def test_busy_time_is_averaged_over_chips():
    ms = 10**6
    s = tracing.summarize(_trace([["op", 0, 10 * ms]],
                                 [["bench.window", 0, 100 * ms]], chips=2))
    assert s.busy_s == pytest.approx(0.010)
    assert s.n_ops == 2


def test_a_trace_without_its_window_or_device_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        tracing.summarize(_trace([], []))
    with pytest.raises(ValueError, match="device"):
        tracing.summarize({"planes": [{"name": "/host:CPU", "lines": [
            {"name": "python", "events": [["bench.window", 0, 10]]}]}]})


def test_summary_of_a_recorded_v5e_trace():
    """0.6 s of a traced ``conv32`` window on one TPU v5e, inside a
    stalled batch walk: 315 op events on ``/device:TPU:0`` and the
    harness's spans on the host."""
    trace = json.loads((DATA / "trace_v5e_conv32.json").read_text())
    ops = [e for p in trace["planes"] if p["name"] == "/device:TPU:0"
           for ln in p["lines"] for e in ln["events"]]
    ops.sort(key=lambda e: e[1])
    # the recorded ops do not overlap, so busy time is their sum
    assert all(b[1] >= a[1] + a[2] for a, b in zip(ops, ops[1:]))
    s = tracing.summarize(trace)
    assert s.window_s == pytest.approx(0.6)
    assert s.n_ops == len(ops) == 315
    assert s.busy_s == pytest.approx(sum(e[2] for e in ops) / 1e9)
    assert 1 - s.busy_s / s.window_s > 0.999
    assert [k for k, _ in s.top_ops[:2]] == ["copy s32[8,32]",
                                             "fusion s32[8,32]"]
    assert sum(d for _, d in s.top_ops) <= s.busy_s + 1e-12
    # the longest gap runs from the last op to the window's end, and
    # the host was inside the backend's walk throughout
    last_end = max(e[1] + e[2] for e in ops)
    assert s.idle_gaps[0] == ["backend.run_workload",
                              pytest.approx((0.6e9 - last_end) / 1e9)]
    assert {n for n, _ in s.idle_gaps} == {"backend.run_workload"}


def test_op_kind_drops_layouts_and_keeps_opcode_and_type():
    assert tracing.op_kind(
        "%copy.1 = s32[8,32]{1,0:T(8,128)} copy(s32[8,32]{1,0:T(8,128)} %a)"
    ) == "copy s32[8,32]"
    assert tracing.op_kind(
        '%call.1 = (s32[8,32]{1,0:T(8,128)}, s32[8,32]{1,0}) custom-call('
        's32[8,32]{1,0} %x), custom_call_target="tpu_custom_call"'
    ) == "custom-call:tpu_custom_call (s32[8,32], s32[8,32])"
    assert tracing.op_kind("not hlo") == "not hlo"
