"""The program's ``kvi.*`` host spans in a hand-made trace: their totals
in the window, the idle gaps they name, and the walk's and engine's
split read from them."""
import json
import random

import pytest

from _perfbench_helpers import DATA
from bench import spans, tracing

ms = 10 ** 6


def _trace(device_events, host_events):
    return {"planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": host_events}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": device_events}]}]}


# one engine run over one walk: staging, the call and a sync, then the
# outputs; the window opens at 0 and closes at 100 ms
HOST = [["bench.window", 0, 100 * ms],
        ["kvi.walk.call", -5 * ms, 2 * ms],           # before the window
        ["engine.run", 5 * ms, 80 * ms],
        ["kvi.engine.run", 6 * ms, 74 * ms],
        ["backend.run_workload", 10 * ms, 70 * ms],
        ["kvi.backend.run_workload", 11 * ms, 68 * ms],
        ["kvi.walk", 12 * ms, 66 * ms],
        ["kvi.walk.stage", 13 * ms, 10 * ms],
        ["kvi.walk.call", 30 * ms, 20 * ms],
        ["kvi.walk.sync", 55 * ms, 20 * ms],
        ["kvi.walk.outputs", 76 * ms, 1 * ms],
        ["kvi.walk.sync", 98 * ms, 4 * ms]]           # cut by the close
DEVICE = [["fusion", 16 * ms, 2 * ms], ["copy", 45 * ms, 1 * ms],
          ["copy", 60 * ms, 1 * ms]]


def test_span_totals_are_clipped_to_the_window():
    s = spans.summarize(_trace(DEVICE, HOST))
    t = s["program_spans"]
    assert t["kvi.walk.call"] == [1, pytest.approx(0.020)]
    assert t["kvi.walk.sync"] == [2, pytest.approx(0.022)]
    assert t["kvi.walk"] == [1, pytest.approx(0.066)]
    assert "engine.run" not in t and "bench.window" not in t


def test_gaps_are_named_by_the_innermost_span_harness_or_program():
    s = spans.summarize(_trace(DEVICE, HOST))
    # 18..45 ms: middle 31.5 in the call, inside backend.run_workload
    # and kvi.walk; 61..100: middle 80.5 in engine.run only (the
    # program's engine span ended at 80, its walk at 78); 0..16: middle
    # 8 in kvi.engine.run; 46..60: middle 53 in kvi.walk between items
    assert s["idle_gaps"] == [["engine.run", pytest.approx(0.039)],
                              ["kvi.walk.call", pytest.approx(0.027)],
                              ["kvi.engine.run", pytest.approx(0.016)],
                              ["kvi.walk", pytest.approx(0.014)]]
    assert s["idle_by_span"] == {
        "engine.run": pytest.approx(0.039),
        "kvi.walk.call": pytest.approx(0.027),
        "kvi.engine.run": pytest.approx(0.016),
        "kvi.walk": pytest.approx(0.014)}


def test_idle_by_span_sums_to_the_idle_time():
    trace = _trace(DEVICE, HOST)
    s = spans.summarize(trace)
    busy = tracing.summarize(trace)
    assert sum(s["idle_by_span"].values()) == \
        pytest.approx(busy.window_s - busy.busy_s)


def test_readings_split_the_walk_and_the_engine():
    t = spans.summarize(_trace(DEVICE, HOST))["program_spans"]
    r = spans.readings(t)
    # per walk: sync 22 ms (one clipped outside the walk counts too),
    # dispatch (the call) 20 ms, self 66 - 22 - 20 ms (staging in it);
    # engine 74 - 68 ms
    assert r == {"walk_sync_ms_per_batch": pytest.approx(22.0),
                 "walk_dispatch_ms_per_batch": pytest.approx(20.0),
                 "walk_self_ms_per_batch": pytest.approx(24.0),
                 "engine_self_ms_per_step": pytest.approx(6.0)}


def test_nothing_to_read_without_program_spans():
    harness = [e for e in HOST if not e[0].startswith("kvi.")]
    s = spans.summarize(_trace(DEVICE, harness))
    assert s["program_spans"] == {}
    assert spans.readings(s["program_spans"]) == {}
    assert {n for n in s["idle_by_span"]} <= {"engine.run",
                                              "backend.run_workload",
                                              "client.loop"}
    # a trace with walks and no engine run reads the walk alone
    walk_only = [e for e in HOST if e[0].startswith("kvi.walk")
                 or not e[0].startswith("kvi.")]
    r = spans.readings(spans.summarize(
        _trace(DEVICE, walk_only))["program_spans"])
    assert set(r) == {"walk_sync_ms_per_batch", "walk_dispatch_ms_per_batch",
                      "walk_self_ms_per_batch"}


def test_recorded_v5e_trace_names_gaps_as_the_harness_does():
    """A trace without program spans names each gap as
    ``bench.tracing.summarize`` does."""
    trace = json.loads((DATA / "trace_v5e_conv32.json").read_text())
    s = spans.summarize(trace)
    assert s["program_spans"] == {}
    assert s["idle_gaps"] == tracing.summarize(trace).idle_gaps


@pytest.mark.parametrize("seed", range(5))
def test_innermost_lookup_agrees_with_name_gap(seed):
    rnd = random.Random(seed)
    names = ["a", "b", "c", "d"]
    pts = [rnd.randrange(0, 40) for _ in range(24)]
    sp = []
    for k in range(0, 24, 2):
        s, e = sorted(pts[k:k + 2])
        sp.append((rnd.choice(names), s, e))
    look = spans.innermost(sp)
    for t2 in range(-2, 84):
        t = t2 / 2
        want = tracing.name_gap((t, t), sp)
        assert (look(t) or "client.loop") == want, t
