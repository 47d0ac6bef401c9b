"""The streamed matmul: B is one main-memory buffer per column, loaded
again for every row of A, so a request's data is B itself. The
instruction stream, and with it every cycle-model statistic, is the one
the program had when each (row, column) pair had a buffer of its own.
The compiled walk stages each buffer once, runs each ``kdotpps`` as one
reduction kernel under the ``kvi.reduce`` scope, and counts them in
``meta["reductions"]``."""
from collections import Counter

import jax
import numpy as np
import pytest

from repro.kvi import get_backend
from repro.kvi.ir import KviOp, ScalarBlock
from repro.kvi.obs import Obs, host
from repro.kvi.pallas_backend import PallasBackend
from repro.kvi.passes import PassPipeline
from repro.kvi.programs import conv2d_program, fft_program, matmul_program
from repro.kvi.scheduler import simulated_profile
from repro.kvi.workload import KviWorkload


def _matmul(n, seed=5):
    rng = np.random.default_rng(seed)
    A, B = (rng.integers(-128, 128, (n, n)).astype(np.int32)
            for _ in range(2))
    return A, B, matmul_program(A, B, shift=2, resident=False)


def _conv(seed):
    rng = np.random.default_rng(seed)
    return conv2d_program(rng.integers(-128, 128, (8, 8)).astype(np.int32),
                          np.arange(-4, 5, dtype=np.int32).reshape(3, 3),
                          shift=3)


def _fft(seed):
    rng = np.random.default_rng(seed)
    re, im = (rng.integers(-2048, 2048, 32).astype(np.int32)
              for _ in range(2))
    return fft_program(re, im)


def test_b_is_one_buffer_per_column_loaded_for_every_row():
    A, B, prog = _matmul(64)
    cols = [m for m in prog.mems if m.name.startswith("bcol")]
    assert [m.name for m in cols] == [f"bcol{j}" for j in range(64)]
    for j, m in enumerate(cols):
        np.testing.assert_array_equal(prog.mem_init[m.id], B[:, j])
    loads = Counter(i.src1.id for i in prog.items
                    if not isinstance(i, ScalarBlock)
                    and i.op is KviOp.KMEMLD)
    assert sum(loads[m.id] for m in cols) == 4096
    assert all(loads[m.id] == 64 for m in cols)
    # the other inputs are A's rows, one load each
    rows = [m for m in prog.mems if not m.is_output and m not in cols]
    assert [m.name for m in rows] == [f"arow{i}" for i in range(64)]
    assert all(loads[m.id] == 1 for m in rows)


# the cycle model's statistics of the streamed matmul8 (shift 2) run
# on three harts under each scheme, as the program had them when B was
# a buffer per (row, column) pair: (finish, busy, stall, idle) per hart
PINNED_MATMUL8 = {
    "shared": [(5547, 1931, 3306, 390), (5587, 1931, 3345, 351),
               (5627, 1931, 3384, 312)],
    "sym_mimd": [(3891, 1931, 1650, 342), (3907, 1931, 1665, 327),
                 (3923, 1931, 1680, 312)],
    "het_mimd": [(3891, 1931, 1650, 342), (3907, 1931, 1665, 327),
                 (3923, 1931, 1680, 312)],
}


def test_cycle_statistics_are_unchanged():
    _, _, prog = _matmul(8)
    assert prog.n_instructions == 464
    res = get_backend("cyclesim").run_workload(
        KviWorkload.replicate(prog, 3), functional=False)
    got = {scheme: [(h.finish_cycle, h.busy_cycles, h.stall_cycles,
                     h.idle_cycles) for h in sim.per_hart]
           for scheme, sim in res.timing.items()}
    assert got == PINNED_MATMUL8
    assert simulated_profile(prog) == {"cycles": 2241, "busy": 1931,
                                       "stall": 0, "idle": 310}


@pytest.mark.parametrize("N", [1, 3])
def test_walk_equals_the_oracle_and_counts_its_reductions(N):
    progs = [_matmul(8, seed)[2] for seed in range(N)]
    wl = KviWorkload.homogeneous(progs)
    obs = Obs.on()
    res = PallasBackend(obs=obs).run_workload(wl)
    want = get_backend("oracle").run_workload(wl)
    for a, b in zip(want.entry_results, res.entry_results):
        assert set(a.outputs) == set(b.outputs)
        for k, v in a.outputs.items():
            assert v.dtype == b.outputs[k].dtype
            np.testing.assert_array_equal(v, b.outputs[k])
    assert res.meta["reductions"] == res.meta["pallas_calls"] == 64
    assert obs.metrics.snapshot()["counters"]["pallas.reductions"] == 64


@pytest.mark.parametrize("build", [_conv, _fft], ids=["conv8", "fft32"])
def test_walks_without_reductions_count_none(build):
    res = PallasBackend().run_workload(
        KviWorkload.homogeneous([build(s) for s in range(2)]))
    assert res.meta["reductions"] == 0 < res.meta["pallas_calls"]


def test_a_buffer_loaded_many_times_is_staged_once():
    _, _, prog = _matmul(8)
    _, args, spec = PallasBackend()._walk_fn(prog, 3)
    (_, mids), = spec.loads
    assert len(mids) == len(set(mids)) == 16       # 8 columns, 8 rows
    assert [a.shape for a in args] == [(3, 16 * 8)]


def _compiled_text(prog, N=2):
    prog = PassPipeline.from_spec(None).run(prog)
    walk, args, spec = PallasBackend(passes=())._walk_fn(prog, N)
    return jax.jit(walk).lower(*args).compile().as_text(), spec


def test_walk_names_its_reductions_and_regions_on_the_cpu():
    text, spec = _compiled_text(_matmul(8)[2])
    assert spec.reduce_calls == 64 and spec.fused_calls == 0
    assert f"jit(walk)/{host.REDUCE_SCOPE}/" in text
    assert host.REGION_SCOPE not in text
    text, spec = _compiled_text(_conv(0))
    assert spec.fused_calls > 0 and spec.reduce_calls == 0
    assert f"jit(walk)/{host.REGION_SCOPE}/" in text
    assert host.REDUCE_SCOPE not in text
