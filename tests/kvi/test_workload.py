"""Workload-level tests: composite batches, hart assignment, batched
Pallas dispatch, the continuous-admission scheduler, and the legacy-shim
deprecation warnings.

The acceptance bar for the hart-aware execution refactor:
  * a composite workload (conv + fft + matmul on harts 0/1/2) runs
    through ``Backend.run_workload()`` on oracle, cyclesim and pallas
    with bit-identical outputs,
  * cyclesim timing for it reproduces the legacy
    ``core/workloads.composite_cycles`` protocol (direct simulate() over
    concatenated per-hart traces),
  * a homogeneous batch of N instances issues as many ``pallas_call``s
    as ONE instance (batch grid dimension), not N of them.
"""
import warnings

import numpy as np
import pytest

from repro.configs.base import KlessydraConfig
from repro.core.simulator import simulate
from repro.kvi import (KviProgramBuilder, KviWorkload, get_backend,
                       structural_signature)
from repro.kvi.workload import HartAssignment, WorkloadEntry
from repro.kvi.cyclesim import CycleSimBackend, default_schemes
from repro.kvi.lowering import lower
from repro.kvi.programs import conv2d_program, fft_program, matmul_program

BACKENDS = ("oracle", "cyclesim", "pallas")


def _saxpy(seed, n=32, scalar=3):
    rng = np.random.default_rng(seed)
    x = rng.integers(-100, 100, n).astype(np.int32)
    b = KviProgramBuilder("saxpy")
    hx = b.mem_in("x", x)
    v = b.vreg("v", n)
    b.kmemld(v, hx)
    b.ksvmulsc(v, v, scalar=scalar)
    b.krelu(v, v)
    hy = b.mem_out("y", n)
    b.kmemstr(hy, v)
    return b.build(), np.maximum(x * scalar, 0).astype(np.int32)


def _small_composite(rng, harts=(0, 1, 2)):
    """conv8 + fft32 + matmul8(streamed) pinned to three harts — the
    paper's composite shape at test-friendly sizes."""
    img = rng.integers(-128, 128, (8, 8)).astype(np.int32)
    filt = rng.integers(-8, 8, (3, 3)).astype(np.int32)
    re = rng.integers(-2048, 2048, 32).astype(np.int32)
    im = rng.integers(-2048, 2048, 32).astype(np.int32)
    A = rng.integers(-64, 64, (8, 8)).astype(np.int32)
    B = rng.integers(-64, 64, (8, 8)).astype(np.int32)
    return KviWorkload.composite({
        harts[0]: [conv2d_program(img, filt, shift=4)],
        harts[1]: [fft_program(re, im)],
        harts[2]: [matmul_program(A, B, shift=2, resident=False)],
    })


def _outputs_equal(a, b):
    assert set(a.outputs) == set(b.outputs)
    for k in a.outputs:
        assert np.array_equal(a.outputs[k], b.outputs[k]), k


class TestWorkloadStructure:
    def test_single_and_replicate(self, rng):
        p, _ = _saxpy(0)
        assert len(KviWorkload.single(p).entries) == 1
        wl = KviWorkload.replicate(p, 3)
        assert [e.hart for e in wl.entries] == [0, 1, 2]
        assert wl.is_homogeneous

    def test_homogeneous_rejects_structural_mismatch(self):
        p1, _ = _saxpy(0, scalar=3)
        p2, _ = _saxpy(1, scalar=5)          # different immediate
        assert structural_signature(p1) != structural_signature(p2)
        with pytest.raises(ValueError, match="structurally identical"):
            KviWorkload.homogeneous([p1, p2])

    def test_assign_harts_round_robin_and_pinning(self, rng):
        progs = [_saxpy(s)[0] for s in range(4)]
        wl = KviWorkload(
            "mix",
            (WorkloadEntry(progs[0], HartAssignment(2)),
             WorkloadEntry(progs[1]),
             WorkloadEntry(progs[2]),
             WorkloadEntry(progs[3], HartAssignment(2))))
        per_hart = wl.assign_harts(3)
        assert per_hart == [[1], [2], [0, 3]]
        with pytest.raises(ValueError, match="hart 2"):
            wl.assign_harts(2)


class TestCompositeWorkload:
    def test_oracle_equals_cyclesim_heterogeneous_batch(self, rng):
        wl = _small_composite(rng)
        ro = get_backend("oracle").run_workload(wl)
        rc = get_backend("cyclesim").run_workload(wl)
        assert len(ro.entry_results) == len(wl.entries) == 3
        for a, b in zip(ro.entry_results, rc.entry_results):
            _outputs_equal(a, b)

    def test_composite_invariant_and_hart_parallelism(self, rng):
        """Paper invariant on a composite workload: sym-MIMD <= het-MIMD
        <= shared, and het-MIMD beats shared by a hart-parallelism
        factor (three independent SPMIs vs one serialized MFU). The
        factor is strongest when per-hart loads are balanced; the
        streamed-matmul composite is LSU-bound (the memory port is
        shared in every scheme), so it clears a lower bar."""
        wl = _small_composite(rng)
        res = get_backend("cyclesim").run_workload(wl, functional=False)
        c = res.cycles
        assert c["sym_mimd"] <= c["het_mimd"] <= c["shared"], c
        assert c["shared"] / c["het_mimd"] > 1.2, c

        # balanced MFU-heavy composite: conv16 x2 / fft64 x2 / matmul16
        img = lambda s: rng.integers(-128, 128, (16, 16)).astype(np.int32)
        filt = rng.integers(-8, 8, (3, 3)).astype(np.int32)
        bal = KviWorkload.composite({
            0: [conv2d_program(img(0), filt, shift=4),
                conv2d_program(img(1), filt, shift=4)],
            1: [fft_program(
                    rng.integers(-2048, 2048, 64).astype(np.int32),
                    rng.integers(-2048, 2048, 64).astype(np.int32)),
                fft_program(
                    rng.integers(-2048, 2048, 64).astype(np.int32),
                    rng.integers(-2048, 2048, 64).astype(np.int32))],
            2: [matmul_program(
                    rng.integers(-64, 64, (16, 16)).astype(np.int32),
                    rng.integers(-64, 64, (16, 16)).astype(np.int32),
                    shift=2, resident=True)],
        })
        c = get_backend("cyclesim").run_workload(
            bal, functional=False).cycles
        assert c["sym_mimd"] <= c["het_mimd"] <= c["shared"], c
        assert c["shared"] / c["het_mimd"] > 1.3, c

    def test_small_composite_three_backends_bit_identical(self, rng):
        wl = _small_composite(rng)
        results = {n: get_backend(n).run_workload(wl) for n in BACKENDS}
        for n in ("cyclesim", "pallas"):
            for a, b in zip(results["oracle"].entry_results,
                            results[n].entry_results):
                _outputs_equal(a, b)

    @pytest.mark.slow
    def test_paper_composite_three_backends_and_legacy_timing(self, rng):
        """Acceptance: conv32 + fft256 + matmul64 on harts 0/1/2 through
        run_workload() on all three backends, bit-identical; cyclesim
        timing reproduces the legacy composite_cycles protocol."""
        from repro.core.workloads import composite_workload
        cfg = KlessydraConfig("het_mimd", M=3, F=1, D=4, spm_kbytes=64)
        reps = {"conv32": 2, "fft256": 2, "matmul64": 1}
        wl = composite_workload(cfg, reps)
        assert [e.hart for e in wl.entries] == [0, 0, 1, 1, 2]

        results = {n: get_backend(n).run_workload(wl) for n in BACKENDS}
        for n in ("cyclesim", "pallas"):
            for a, b in zip(results["oracle"].entry_results,
                            results[n].entry_results):
                _outputs_equal(a, b)

        # legacy protocol: concatenated per-hart traces, direct simulate()
        for scheme, scfg in default_schemes().items():
            progs = [[], [], []]
            for e in wl.entries:
                progs[e.hart].extend(lower(e.program, scfg).items)
            legacy = simulate(scfg, progs)
            got = results["cyclesim"].timing[scheme]
            assert got.cycles == legacy.cycles, scheme
            assert ([h.finish_cycle for h in got.per_hart] ==
                    [h.finish_cycle for h in legacy.per_hart]), scheme

    def test_composite_cycles_helper_matches_run_workload(self):
        """core.workloads.composite_cycles is now a thin wrapper — its
        numbers must equal a direct run_workload of the same workload."""
        from repro.core.workloads import (COMPOSITE_KERNELS,
                                          composite_cycles,
                                          composite_workload)
        cfg = KlessydraConfig("HetMIMD", M=3, F=1, D=8)
        reps = {"conv32": 2, "fft256": 1, "matmul64": 1}
        helper = composite_cycles(cfg, reps)
        res = CycleSimBackend(schemes={"s": cfg}).run_workload(
            composite_workload(cfg, reps), functional=False)
        sim = res.timing["s"]
        for h, k in enumerate(COMPOSITE_KERNELS):
            assert helper[k] == sim.per_hart[h].finish_cycle / reps[k]
        assert helper["total_cycles"] == sim.cycles


class TestBatchedPallas:
    def test_homogeneous_batch_single_pallas_call(self):
        """N instances of an element-wise program must issue exactly as
        many pallas_calls as ONE instance (the batch grid dimension),
        not N."""
        from repro.kvi.pallas_backend import PallasBackend
        progs, wants = zip(*[_saxpy(s) for s in range(6)])

        solo = PallasBackend()
        solo.run(progs[0])
        calls_for_one = solo.fused_calls + solo.reduce_calls
        assert calls_for_one == 1

        batched = PallasBackend()
        res = batched.run_workload(KviWorkload.homogeneous(progs))
        assert batched.fused_calls + batched.reduce_calls == calls_for_one
        for r, want in zip(res.entry_results, wants):
            assert np.array_equal(r.outputs["y"], want)

    def test_batched_reductions_match_oracle(self, rng):
        """A homogeneous batch with kdotp/kvred goes through vmapped
        reduction kernels — still one launch per reduction site."""
        from repro.kvi.pallas_backend import PallasBackend
        progs = []
        for s in range(3):
            r = np.random.default_rng(s)
            A = r.integers(-64, 64, (4, 4)).astype(np.int32)
            B = r.integers(-64, 64, (4, 4)).astype(np.int32)
            progs.append(matmul_program(A, B, shift=2, resident=False))
        wl = KviWorkload.homogeneous(progs)
        pb = PallasBackend()
        rp = pb.run_workload(wl)
        ro = get_backend("oracle").run_workload(wl)
        for a, b in zip(ro.entry_results, rp.entry_results):
            _outputs_equal(a, b)
        # 16 kdotpps sites in a 4x4 streamed matmul, each ONE vmapped
        # launch for the whole batch
        assert pb.reduce_calls == 16

    def test_heterogeneous_workload_grouped_by_structure(self, rng):
        """A workload mixing two structures batches per group."""
        from repro.kvi.pallas_backend import PallasBackend
        sax = [_saxpy(s)[0] for s in range(3)]
        other = [_saxpy(s, n=16, scalar=7)[0] for s in range(2)]
        wl = KviWorkload("mix", tuple(WorkloadEntry(p)
                                      for p in sax + other))
        assert not wl.is_homogeneous
        pb = PallasBackend()
        res = pb.run_workload(wl)
        assert res.meta["groups"] == 2
        assert pb.fused_calls == 2            # one per structural group
        ro = get_backend("oracle").run_workload(wl)
        for a, b in zip(ro.entry_results, res.entry_results):
            _outputs_equal(a, b)

    def test_run_wrapper_equals_workload_entry(self, rng):
        p, want = _saxpy(9)
        for name in BACKENDS:
            r1 = get_backend(name).run(p)
            r2 = get_backend(name).run_workload(
                KviWorkload.single(p)).entry_result(0)
            _outputs_equal(r1, r2)
            assert np.array_equal(r1.outputs["y"], want)


    def test_warm_walk_builds_nothing(self):
        """A second batch of the same structure and size reuses the
        compiled walk: no build, no kernel-cache miss, one hit."""
        from repro.kvi.pallas_backend import PallasBackend
        progs, wants = zip(*[_saxpy(s) for s in range(4)])
        pb = PallasBackend()
        cold = pb.run_workload(KviWorkload.homogeneous(progs))
        warm = pb.run_workload(KviWorkload.homogeneous(progs[::-1]))
        assert cold.meta["walks_built"] == 1
        assert cold.meta["compile_cache"]["misses"] > 0
        assert warm.meta["walks"] == 1
        assert warm.meta["walks_built"] == 0
        assert warm.meta["compile_cache"] == {"hits": 1, "misses": 0}
        for r, want in zip(warm.entry_results, wants[::-1]):
            assert np.array_equal(r.outputs["y"], want)

    @pytest.mark.parametrize("change", ["N", "structure"])
    def test_new_batch_size_or_structure_builds_one_walk(self, change):
        from repro.kvi.pallas_backend import PallasBackend
        pb = PallasBackend()
        pb.run_workload(KviWorkload.homogeneous(
            [_saxpy(s)[0] for s in range(4)]))
        if change == "N":
            progs, wants = zip(*[_saxpy(s) for s in range(3)])
        else:
            progs, wants = zip(*[_saxpy(s, scalar=5) for s in range(4)])
        res = pb.run_workload(KviWorkload.homogeneous(progs))
        assert res.meta["walks"] == res.meta["walks_built"] == 1
        assert pb.walks_built == 2
        for r, want in zip(res.entry_results, wants):
            assert np.array_equal(r.outputs["y"], want)

    @pytest.mark.parametrize("n_batch", [1, 3])
    def test_stored_buffer_is_read_back(self, n_batch):
        """A ``kmemld`` of a buffer an earlier ``kmemstr`` wrote reads
        what was stored, inside the one compiled walk."""
        from repro.kvi.pallas_backend import PallasBackend
        progs = []
        for s in range(n_batch):
            x = np.random.default_rng(s).integers(-100, 100, 16)
            b = KviProgramBuilder("round_trip")
            hx = b.mem_in("x", x.astype(np.int32))
            v, w = b.vreg("v", 16), b.vreg("w", 16)
            b.kmemld(v, hx)
            b.ksvmulsc(v, v, scalar=3)
            hy = b.mem_out("y", 16)
            b.kmemstr(hy, v)
            b.kmemld(w, hy)
            b.kaddv(w, w, v)
            hz = b.mem_out("z", 16)
            b.kmemstr(hz, w)
            progs.append(b.build())
        wl = KviWorkload.homogeneous(progs)
        res = PallasBackend(passes=()).run_workload(wl)
        ro = get_backend("oracle", passes=()).run_workload(wl)
        assert res.meta["host_syncs"] == 1
        for p, a, b in zip(progs, ro.entry_results, res.entry_results):
            _outputs_equal(a, b)
            assert np.array_equal(b.outputs["z"], 6 * p.mem_init[0])


class TestScheduler:
    def test_earliest_finish_packing(self):
        from repro.kvi.scheduler import HartScheduler
        sched = HartScheduler(n_harts=2,
                              estimator=lambda p: p.meta["cost"])
        costs = [100, 10, 10, 10, 80]
        for i, c in enumerate(costs):
            b = KviProgramBuilder(f"p{i}")
            h = b.mem_in("x", np.ones(4, np.int32))
            v = b.vreg("v", 4)
            b.kmemld(v, h)
            ho = b.mem_out("y", 4)
            b.kmemstr(ho, v)
            sched.submit(b.build(cost=c))
        wl = sched.dispatch()
        # p0(100) -> hart 0; p1..p3 fill hart 1; p4(80) back on hart 1
        assert [e.hart for e in wl.entries] == [0, 1, 1, 1, 1]
        assert sched.hart_loads == [100, 110]

    def test_dispatch_deterministic_under_equal_finish_times(self):
        """Regression: equal accumulated finish times tie-break on
        submission order (the hart that became free EARLIEST wins), not
        on an arbitrary hart-index race — and dispatch is reproducible
        run to run."""
        from repro.kvi.scheduler import HartScheduler

        def build(i):
            b = KviProgramBuilder(f"p{i}")
            h = b.mem_in("x", np.ones(4, np.int32))
            v = b.vreg("v", 4)
            b.kmemld(v, h)
            b.kmemstr(b.mem_out("y", 4), v)
            return b.build()

        costs = [2, 4, 2, 2, 2]

        def placements():
            sched = HartScheduler(
                n_harts=2, estimator=lambda p: costs[int(p.name[1:])])
            for i in range(len(costs)):
                sched.submit(build(i))
            return [e.hart for e in sched.dispatch().entries]

        # p0->h0(2), p1->h1(4), p2->h0(now 4). p3 sees BOTH harts free at
        # 4: h1 got there first (p1 was admitted before p2), so p3->h1.
        assert placements() == [0, 1, 0, 1, 0]
        assert placements() == placements()

    def test_scheduled_workload_executes(self, rng):
        from repro.kvi.scheduler import HartScheduler
        sched = HartScheduler(n_harts=3)
        wants = []
        for s in range(5):
            p, want = _saxpy(s)
            sched.submit(p)
            wants.append(want)
        res = sched.run(get_backend("cyclesim"))
        assert res.cycles["sym_mimd"] <= res.cycles["shared"]
        for r, want in zip(res.entry_results, wants):
            assert np.array_equal(r.outputs["y"], want)


class TestDeprecationShims:
    def test_program_builder_warns(self):
        from repro.core.programs import ProgramBuilder
        cfg = KlessydraConfig("x", M=1, F=1, D=4)
        with pytest.warns(DeprecationWarning,
                          match="repro.kvi.KviProgramBuilder"):
            ProgramBuilder(cfg)

    def test_run_vops_warns_and_still_works(self):
        import jax.numpy as jnp
        from repro.kernels.kvi_vops import run_vops
        x = jnp.arange(-8, 8, dtype=jnp.int32)
        with pytest.warns(DeprecationWarning, match="KviProgramBuilder"):
            out = run_vops([("ksvmulsc", 1, 0, None, 3),
                            ("krelu", 1, 1, None, 0)], [x])
        want = np.maximum(np.arange(-8, 8) * 3, 0).astype(np.int32)
        assert np.array_equal(np.asarray(out), want)

    def test_legacy_builders_do_not_warn(self, rng):
        """The build_* shims lower canonical KVI programs without the
        ProgramBuilder warning (they are the supported compat path)."""
        from repro.core.programs import build_conv2d, conv2d_result
        cfg = KlessydraConfig("x", M=1, F=1, D=4, spm_kbytes=64)
        img = rng.integers(-16, 16, (4, 4)).astype(np.int32)
        filt = rng.integers(-4, 4, (3, 3)).astype(np.int32)
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            prog = build_conv2d(cfg, img, filt)
            prog.builder.run_functional()
        assert conv2d_result(prog, 4).shape == (4, 4)


class TestHartUtilization:
    """The per-hart busy/stall/idle breakdown surfaced from HartStats
    through SimResult into WorkloadResult (previously discarded)."""

    def test_breakdown_sums_to_total_cycles(self, rng):
        wl = _small_composite(rng)
        res = CycleSimBackend().run_workload(wl, functional=False)
        util = res.hart_utilization
        assert util is not None and set(util) == set(res.cycles)
        for scheme, harts in util.items():
            total = res.cycles[scheme]
            for h in harts:
                assert h["busy"] + h["stall"] + h["idle"] == total, scheme
                assert h["busy"] >= 0 and h["stall"] >= 0 \
                    and h["idle"] >= 0
                assert h["total"] == total
                assert h["utilization"] == pytest.approx(
                    h["busy"] / max(total, 1))

    def test_contended_scheme_stalls_more(self, rng):
        """The shared scheme's single MFU serializes three harts — they
        must spend at least as many stall cycles as under sym-MIMD."""
        prog, _ = _saxpy(0, n=64)
        wl = KviWorkload.replicate(prog, 3)
        res = CycleSimBackend().run_workload(wl, functional=False)
        util = res.hart_utilization
        stall = {s: sum(h["stall"] for h in hs)
                 for s, hs in util.items()}
        assert stall["sym_mimd"] <= stall["shared"]

    def test_timingless_backend_returns_none(self, rng):
        prog, _ = _saxpy(1)
        res = get_backend("oracle").run_workload(KviWorkload.single(prog))
        assert res.hart_utilization is None
