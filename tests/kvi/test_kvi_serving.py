"""Serving-engine tests: load generation, continuous admission,
signature batching, compiled-kernel reuse and the serving report.

The acceptance bar for the serving tentpole:
  * seeded arrival streams and instantiated request data are
    deterministic (and the canonical serving report byte-identical
    across runs),
  * continuous admission has no head-of-line blocking — a long matmul
    on one hart does not delay conv latencies on the others,
  * with prewarming, the serving loop itself never compiles: the
    kernel-cache steady-state hit rate is exactly 1.0,
  * batched execution is bit-identical to the scalar oracle and issues
    fewer ``pallas_call``s per request than one-request-at-a-time
    dispatch.
"""
import numpy as np
import pytest

from repro.kvi.scheduler import HartScheduler
from repro.kvi.serving import (SMOKE_MIX, RequestSpec, ServeEngine,
                               bucket_sizes, canonical_report, load_trace,
                               make_templates, poisson_arrivals, save_trace,
                               template_key)
from repro.kvi.workload import structural_signature


@pytest.fixture(scope="module")
def templates():
    return make_templates(SMOKE_MIX, smoke=True, seed=0)


@pytest.fixture(scope="module")
def specs(templates):
    return poisson_arrivals(templates, 32, 80.0, n_clients=50, seed=0)


# ---------------------------------------------------------------------------
# Load generation
# ---------------------------------------------------------------------------


class TestLoad:
    def test_poisson_arrivals_deterministic(self, templates):
        a = poisson_arrivals(templates, 40, 50.0, seed=7)
        b = poisson_arrivals(templates, 40, 50.0, seed=7)
        assert a == b
        c = poisson_arrivals(templates, 40, 50.0, seed=8)
        assert a != c
        assert all(x.t <= y.t for x, y in zip(a, a[1:]))

    def test_template_instances_share_structure(self, templates):
        tpl = templates[template_key("conv", 4)]
        p1 = tpl.instantiate(seed=0, rid=1)
        p2 = tpl.instantiate(seed=0, rid=2)
        # same structural signature (batchable), different data
        assert structural_signature(p1) == structural_signature(p2)
        assert structural_signature(p1) == tpl.signature
        assert p1.items is tpl.program.items      # structure shared
        img = next(m for m in p1.mems if m.name in tpl.data_mems)
        assert not np.array_equal(p1.mem_init[img.id],
                                  p2.mem_init[img.id])

    def test_instantiate_deterministic_and_order_free(self, templates):
        tpl = templates[template_key("matmul", 2)]
        a = tpl.instantiate(seed=3, rid=5)
        b = tpl.instantiate(seed=3, rid=5)
        for m in tpl.program.mems:
            assert np.array_equal(a.mem_init[m.id], b.mem_init[m.id])

    def test_constants_and_outputs(self, templates):
        tpl = templates[template_key("conv", 4)]
        p = tpl.instantiate(seed=0, rid=9)
        for m in tpl.program.mems:
            if m.is_output:
                assert not p.mem_init[m.id].any()
            elif m.name not in tpl.data_mems:
                assert np.array_equal(p.mem_init[m.id],
                                      tpl.program.mem_init[m.id])

    def test_trace_roundtrip(self, templates, specs, tmp_path):
        path = str(tmp_path / "trace.json")
        save_trace(specs, path)
        assert load_trace(path) == sorted(specs, key=lambda s: s.t)

    def test_template_profile_nonzero(self, templates):
        for tpl in templates.values():
            assert tpl.est_cycles > 0
            assert tpl.profile["busy"] > 0


# ---------------------------------------------------------------------------
# Continuous admission (scheduler.admit)
# ---------------------------------------------------------------------------


class TestAdmit:
    def test_admit_earliest_finish_first(self, templates):
        sched = HartScheduler(n_harts=3, estimator=lambda p: 100)
        prog = templates[template_key("conv", 4)].program
        tickets = [sched.admit(prog, now=0) for _ in range(5)]
        assert [t.hart for t in tickets] == [0, 1, 2, 0, 1]
        assert [t.start_est for t in tickets] == [0, 0, 0, 100, 100]
        assert sched.hart_free == [200, 200, 100]

    def test_admit_respects_arrival_time(self):
        sched = HartScheduler(n_harts=2, estimator=lambda p: 10)
        t1 = sched.admit(None, now=0)
        t2 = sched.admit(None, now=50)    # machine idle until arrival
        assert t1.finish_est == 10
        assert t2.start_est == 50 and t2.finish_est == 60

    def test_no_head_of_line_blocking(self):
        # one long program occupies hart 0; short ones flow through the
        # other harts without queueing behind it
        ests = iter([10_000, 10, 10, 10, 10])
        sched = HartScheduler(n_harts=3,
                              estimator=lambda p: next(ests))
        long = sched.admit(None, now=0)
        shorts = [sched.admit(None, now=0) for _ in range(4)]
        assert long.hart == 0
        assert all(s.hart != 0 for s in shorts)
        assert max(s.finish_est for s in shorts) == 20


# ---------------------------------------------------------------------------
# Engine (schedule-only: no jax)
# ---------------------------------------------------------------------------


class TestEngineScheduleOnly:
    def test_bucket_sizes(self):
        assert bucket_sizes(13, 8) == [8, 4, 1]
        assert bucket_sizes(8, 8) == [8]
        assert bucket_sizes(3, 8) == [2, 1]
        assert bucket_sizes(5, 2) == [2, 2, 1]
        assert bucket_sizes(0, 8) == []
        assert sum(bucket_sizes(117, 16)) == 117

    def test_max_batch_must_be_power_of_two(self, templates):
        with pytest.raises(ValueError, match="power of two"):
            ServeEngine(templates, max_batch=6)

    def test_unknown_template_rejected(self, templates):
        eng = ServeEngine(templates, backend=None)
        with pytest.raises(KeyError, match="fft@64"):
            eng.run([RequestSpec(0, "fft", 8)])

    def test_report_deterministic(self, templates, specs):
        a = ServeEngine(templates, backend=None, seed=0).run(specs)
        b = ServeEngine(templates, backend=None, seed=0).run(specs)
        assert canonical_report(a) == canonical_report(b)

    def test_latency_and_throughput_fields(self, templates, specs):
        rep = ServeEngine(templates, backend=None, seed=0).run(specs)
        assert rep["throughput"]["requests"] == len(specs)
        lat = rep["latency_cycles"]
        assert 0 < lat["p50"] <= lat["p95"] <= lat["p99"] <= lat["max"]
        # every request is accounted to exactly one template
        assert sum(v["n"] for v in rep["per_template"].values()) \
            == len(specs)
        # wave sizes partition the stream
        assert sum(int(k) * v for k, v in rep["wave_sizes"].items()) \
            == len(specs)

    def test_utilization_invariant(self, templates, specs):
        rep = ServeEngine(templates, backend=None, seed=0).run(specs)
        makespan = rep["throughput"]["makespan_cycles"]
        assert makespan > 0
        for h in rep["hart_utilization"]:
            assert h["busy"] + h["stall"] + h["idle"] == makespan
            assert 0.0 <= h["utilization"] <= 1.0

    def test_batching_flag_does_not_change_schedule(self, templates,
                                                    specs):
        # batching only changes wall execution; the virtual-time
        # schedule (latencies, utilization, waves) is identical
        a = ServeEngine(templates, backend=None, batching=True,
                        seed=0).run(specs)
        b = ServeEngine(templates, backend=None, batching=False,
                        seed=0).run(specs)
        for k in ("latency_cycles", "hart_utilization", "wave_sizes",
                  "throughput"):
            assert a[k] == b[k]
        assert b["batch_sizes"] == {"1": len(specs)}

    def test_conv_p99_unharmed_by_long_matmul(self, templates):
        # head-of-line regression gate: convs keep flowing while a
        # long-running matmul occupies one hart. Every conv must beat
        # the matmul's own completion — with 3 harts and one matmul in
        # front, queueing convs behind it would violate this wildly.
        conv = templates[template_key("conv", 4)]
        mm = templates[template_key("matmul", 2)]
        long_est = 50 * conv.est_cycles
        orig_profile = mm.profile
        mm.profile = dict(mm.profile, cycles=long_est)
        try:
            stream = [RequestSpec(0, "matmul", 2)] + [
                RequestSpec(1 + i, "conv", 4) for i in range(8)]
            rep = ServeEngine(templates, n_harts=3,
                              backend=None, seed=0).run(stream)
            conv_p99 = rep["per_template"][conv.name][
                "latency_cycles"]["p99"]
            assert conv_p99 < long_est
            # 8 convs over 2 remaining harts: 4 rounds of solo latency
            assert conv_p99 <= 4 * conv.est_cycles + 1
        finally:
            mm.profile = orig_profile

    def test_idle_machine_advances_to_next_arrival(self, templates):
        # widely spaced arrivals: each request is its own wave, latency
        # equals the solo estimate (no queueing at all)
        tpl = templates[template_key("conv", 4)]
        stream = [RequestSpec(i * 10 * tpl.est_cycles, "conv", 4)
                  for i in range(4)]
        rep = ServeEngine(templates, backend=None, seed=0).run(stream)
        assert rep["wave_sizes"] == {"1": 4}
        assert rep["latency_cycles"]["max"] == tpl.est_cycles


# ---------------------------------------------------------------------------
# Engine + Pallas backend (execution, cache, speedup)
# ---------------------------------------------------------------------------


class TestEnginePallas:
    @pytest.fixture(scope="class")
    def served(self, templates, specs):
        from repro.kvi.backend import get_backend
        backend = get_backend("pallas", passes=())
        engine = ServeEngine(templates, backend=backend, seed=0)
        report = engine.run(specs)
        return engine, backend, report

    @pytest.mark.slow
    def test_prewarm_means_zero_loop_compiles(self, served):
        _, _, rep = served
        cc = rep["compile_cache"]
        assert cc["loop_misses"] == 0
        assert cc["last_miss_step"] == -1
        assert cc["steady_hit_rate"] == 1.0
        assert cc["hits"] > 0

    @pytest.mark.slow
    def test_batch_sizes_capped_and_cover_stream(self, served, specs):
        engine, _, rep = served
        total = sum(int(k) * v for k, v in rep["batch_sizes"].items())
        assert total == len(specs)
        assert all(int(k) <= engine.max_batch
                   for k in rep["batch_sizes"])
        # power-of-two buckets only
        assert all(int(k) & (int(k) - 1) == 0
                   for k in rep["batch_sizes"])

    @pytest.mark.slow
    def test_outputs_match_oracle(self, templates):
        from repro.kvi.backend import get_backend
        from repro.kvi.workload import KviWorkload
        oracle = get_backend("oracle")
        pallas = get_backend("pallas", passes=())
        tpl = templates[template_key("conv", 4)]
        progs = [tpl.instantiate(seed=0, rid=100 + i) for i in range(4)]
        res = pallas.run_workload(KviWorkload.homogeneous(progs))
        for prog, got in zip(progs, res.entry_results):
            want = oracle.run(prog)
            for k in want.outputs:
                assert np.array_equal(want.outputs[k], got.outputs[k]), k

    @pytest.mark.slow
    def test_batching_issues_fewer_pallas_calls(self, templates, specs,
                                                 served):
        # signature batching runs a whole bucket in one pallas_call per
        # fused segment: fewer dispatches per request than serving the
        # same stream one request at a time (a count, not a CPU timing)
        from repro.kvi.backend import get_backend
        _, _, batched = served
        unbatched = ServeEngine(templates,
                                backend=get_backend("pallas", passes=()),
                                batching=False, seed=0).run(specs)
        b = batched["throughput"]["pallas_calls_per_request"]
        u = unbatched["throughput"]["pallas_calls_per_request"]
        assert 0 < b < u, (b, u)


# ---------------------------------------------------------------------------
# KernelCache unit behaviour
# ---------------------------------------------------------------------------


class TestKernelCache:
    def test_hit_miss_counters(self):
        from repro.kvi.pallas_backend import KernelCache
        cache = KernelCache()
        built = []

        def build():
            built.append(1)
            return lambda: 42

        assert cache.get(("k", 1), build)() == 42
        assert cache.get(("k", 1), build)() == 42
        assert cache.get(("k", 2), build)() == 42
        assert cache.stats == {"hits": 1, "misses": 2, "entries": 2}
        assert len(built) == 2
        assert len(cache) == 2
        cache.clear()
        assert len(cache) == 0
        assert cache.stats["hits"] == 0

    @pytest.mark.slow
    def test_backend_reports_per_call_deltas(self):
        from repro.kvi.backend import get_backend
        from repro.kvi.serving import make_templates
        from repro.kvi.workload import KviWorkload
        tpls = make_templates((("conv", 4),), smoke=True, seed=0)
        tpl = next(iter(tpls.values()))
        progs = [tpl.instantiate(0, i) for i in range(2)]
        backend = get_backend("pallas", passes=())
        first = backend.run_workload(KviWorkload.homogeneous(progs))
        again = backend.run_workload(KviWorkload.homogeneous(progs))
        assert first.meta["compile_cache"]["misses"] > 0
        assert again.meta["compile_cache"]["misses"] == 0
        assert again.meta["compile_cache"]["hits"] > 0
