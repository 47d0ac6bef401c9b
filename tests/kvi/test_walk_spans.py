"""The served path's host spans and counters: a batched walk is one
compiled device program, so under a ``jax.profiler`` session it leaves
one ``kvi.walk.call`` and one ``kvi.walk.sync`` nested in its
``kvi.walk`` (and a ``kvi.walk.build`` only where it was compiled), and
``run_workload``'s ``meta`` counts the walks, their builds, their one
device->host fetch each and the ``pallas_call``s they issue; the
engine's ``kvi.engine.*`` spans frame the backend's. Outputs stay
bit-identical to the oracle."""
import glob
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.kvi import get_backend
from repro.kvi.ir import KviOp, ScalarBlock
from repro.kvi.obs import host, host_span
from repro.kvi.passes.fusion import META_KEY, plan_fusion_regions
from repro.kvi.programs import conv2d_program, fft_program, matmul_program
from repro.kvi.workload import KviWorkload

N = 3
SRC = str(Path(__file__).resolve().parents[2] / "src")


def _conv(rng):
    img = rng.integers(-128, 128, (8, 8)).astype(np.int32)
    filt = np.arange(-4, 5, dtype=np.int32).reshape(3, 3)
    return conv2d_program(img, filt, shift=3)


def _fft(rng):
    re, im = (rng.integers(-2048, 2048, 32).astype(np.int32)
              for _ in range(2))
    return fft_program(re, im)


def _matmul(rng):
    a, b = (rng.integers(-64, 64, (4, 4)).astype(np.int32)
            for _ in range(2))
    return matmul_program(a, b, shift=2, resident=False)


def _profiled(log_dir, fn):
    """``fn()``'s result and the ``kvi.*`` host spans its run left in
    the profiler's trace, as ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for p in ProfileData.from_file(path).planes
             if not p.name.startswith("/device:")
             for ln in p.lines for e in ln.events
             if e.name.startswith(host.PREFIX)]
    return out, spans


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _planned_calls(program):
    """The ``pallas_call``s one walk of the program issues, from its
    fusion plan: one per planned region, one per reduction outside the
    regions (a program with nothing to fuse carries no plan)."""
    plan = program.meta.get(META_KEY) or plan_fusion_regions(program)
    fused = plan.member_items()
    moves = (KviOp.KMEMLD, KviOp.KMEMSTR, KviOp.KVCP)
    return len(plan.regions), sum(
        1 for idx, it in enumerate(program.items)
        if not isinstance(it, ScalarBlock) and idx not in fused
        and it.op not in moves)


def _by_name(spans):
    by_name = {}
    for s in spans:
        by_name.setdefault(s[0], []).append(s)
    return by_name


@pytest.mark.parametrize("build,kinds", [
    (_conv, {"regions"}),
    (_fft, {"regions"}),
    (_matmul, {"reductions"})],
    ids=["conv8", "fft32", "matmul4_streamed"])
def test_walk_is_one_compiled_call(build, kinds, rng, tmp_path):
    progs = [build(rng) for _ in range(N)]
    backend = get_backend("pallas")
    wl = KviWorkload.homogeneous(progs, name="walk-spans")
    oracle = get_backend("oracle")
    for run, built in (("cold", True), ("warm", False)):
        res, spans = _profiled(tmp_path / run,
                               lambda: backend.run_workload(wl))
        by_name = _by_name(spans)
        call, = by_name[host.RUN_WORKLOAD]
        prepare, = by_name[host.PREPARE]
        walk, = by_name[host.WALK]
        assert _inside(prepare, call) and _inside(walk, call)
        assert prepare[2] <= walk[1]
        for name in (host.WALK_STAGE, host.WALK_CALL, host.WALK_SYNC,
                     host.WALK_OUTPUTS):
            span, = by_name[name]
            assert _inside(span, walk), name
        assert by_name[host.WALK_CALL][0][2] <= by_name[host.WALK_SYNC][0][1]
        assert len(by_name.get(host.WALK_BUILD, ())) == built
        assert set(by_name) <= set(host.SPANS)

        regions, reductions = _planned_calls(res.workload.entries[0].program)
        assert {k for k, n in (("regions", regions),
                               ("reductions", reductions)) if n} == kinds
        assert res.meta["walks"] == res.meta["host_syncs"] == 1
        assert res.meta["walks_built"] == built
        assert "eager_ops" not in res.meta
        assert res.meta["pallas_calls"] == regions + reductions
        assert res.meta["reductions"] == reductions
        cc = res.meta["compile_cache"]
        assert (cc["misses"] > 0) if built else cc == {"hits": 1,
                                                        "misses": 0}
        for prog, got in zip(progs, res.entry_results):
            for k, v in oracle.run(prog).outputs.items():
                assert np.array_equal(v, got.outputs[k]), k


def test_each_structure_is_a_walk_of_its_own(rng, tmp_path):
    progs = [_conv(rng), _matmul(rng), _conv(rng)]
    backend = get_backend("pallas")
    wl = KviWorkload.composite({h: [p] for h, p in enumerate(progs)})
    res, spans = _profiled(tmp_path, lambda: backend.run_workload(wl))
    by_name = _by_name(spans)
    walks = by_name[host.WALK]
    assert res.meta["groups"] == res.meta["walks"] == len(walks) == 2
    assert res.meta["walks_built"] == res.meta["host_syncs"] == 2
    for name in (host.WALK_BUILD, host.WALK_CALL, host.WALK_SYNC):
        assert len(by_name[name]) == 2
        assert all(any(_inside(s, w) for w in walks)
                   for s in by_name[name])
    oracle = get_backend("oracle")
    for prog, got in zip(progs, res.entry_results):
        for k, v in oracle.run(prog).outputs.items():
            assert np.array_equal(v, got.outputs[k]), k


def test_counters_are_per_call_without_a_profiler(rng):
    progs = [_conv(rng) for _ in range(2)]
    backend = get_backend("pallas")
    first = backend.run_workload(KviWorkload.homogeneous(progs))
    again = backend.run_workload(KviWorkload.homogeneous(progs))
    assert first.meta["host_syncs"] == again.meta["host_syncs"] == 1
    assert first.meta["pallas_calls"] == again.meta["pallas_calls"] > 0
    assert (first.meta["walks_built"], again.meta["walks_built"]) == (1, 0)
    assert backend.host_syncs == 2
    assert backend.walks_built == 1


def test_engine_spans_frame_the_backend(tmp_path):
    from repro.kvi.serving import RequestSpec, ServeEngine, make_templates
    tpls = make_templates((("conv", 4),), smoke=True, seed=0)
    # 5 requests at t=0 fill one step of buckets 4 and 1; a sixth later
    # opens a second step
    specs = [RequestSpec(0, "conv", 4, client=i) for i in range(5)]
    specs.append(RequestSpec(10 ** 9, "conv", 4, client=5))
    engine = ServeEngine(tpls, backend=get_backend("pallas", passes=()),
                         prewarm=False, seed=0)
    _, spans = _profiled(tmp_path, lambda: engine.run(specs))

    by_name = {}
    for s in spans:
        by_name.setdefault(s[0], []).append(s)
    run, = by_name[host.ENGINE_RUN]
    buckets = [b for st in engine.steps for b in st.buckets]
    assert buckets == [4, 1, 1]
    assert len(by_name[host.ENGINE_ADMIT]) == len(engine.steps) == 2
    assert len(by_name[host.ENGINE_INSTANTIATE]) == len(buckets)
    assert len(by_name[host.RUN_WORKLOAD]) == len(buckets)
    assert len(by_name[host.ENGINE_REPORT]) == 1
    assert all(_inside(s, run) for s in spans if s is not run)
    # each bucket is instantiated, then handed to the backend
    for made, call in zip(by_name[host.ENGINE_INSTANTIATE],
                          by_name[host.RUN_WORKLOAD]):
        assert made[2] <= call[1]


def test_host_span_without_jax_is_a_no_op(monkeypatch):
    monkeypatch.delitem(sys.modules, "jax")
    with host_span(host.ENGINE_ADMIT) as span:
        assert span is None


def test_schedule_only_engine_imports_no_jax():
    code = ("import sys\n"
            "from repro.kvi.serving import (SMOKE_MIX, ServeEngine,\n"
            "                               make_templates, poisson_arrivals)\n"
            "t = make_templates(SMOKE_MIX, smoke=True, seed=0)\n"
            "ServeEngine(t, backend=None).run(poisson_arrivals(t, 8, 40.0))\n"
            "sys.exit(int('jax' in sys.modules))\n")
    p = subprocess.run([sys.executable, "-c", code],
                       env=dict(os.environ, PYTHONPATH=SRC),
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
