"""The ``kvi-matmul64`` deployment on the CPU at a small size: its kernel
file builds the streamed program the repo builds directly, its plain
reference wraps and shifts as ``kdotpps`` does, a small streamed cell is
``correct`` and its 16-bit control is not, its work count, and the
reader of the reduction kernel's share over synthetic trace
summaries."""
import json
import time

import numpy as np
import pytest

from _perfbench_helpers import (CPU, DATA, no_compile_cache,  # noqa: F401
                                small_bench)
from bench import records, run as bench_run, spec
from bench.kernels import matmul

pytestmark = pytest.mark.usefixtures("no_compile_cache")

CELL = "tiny.matmul.closed"


@pytest.fixture
def tiny_matmul(tmp_path):
    """A streamed matmul8 configuration, cut from ``kvi-matmul64`` in
    its sizes alone, served by the closed-loop mix of the small cells;
    returns the benchmark with its cell and the directory of its files."""
    config = dict(spec.load_config("kvi-matmul64"), name="tiny-matmul",
                  n=8, m=8, p=8, data_mems=[f"bcol{j}" for j in range(8)])
    for kind, name, data in (("configs", "tiny-matmul", config),
                             ("traffic", "closed4",
                              spec.load_traffic("closed4", DATA))):
        (tmp_path / kind).mkdir()
        (tmp_path / kind / f"{name}.json").write_text(json.dumps(data))
    bench = small_bench()
    bench["workloads"].append({"name": CELL, "config": "tiny-matmul",
                               "traffic": "closed4", "chips": 1})
    return bench, tmp_path


def _run(tiny, **kw):
    bench, base = tiny
    return bench_run.execute(bench, CELL, 2**31 + 11, 0.5, False, CPU,
                             time.perf_counter(), base=base, **kw)


def test_small_matmul_cell_is_correct_and_its_line_has_the_schema(
        tiny_matmul):
    run, line = _run(tiny_matmul)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"latency_p50_ms", "latency_p95_ms",
                                    "throughput_rps", "setup_s"}
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in line["checks"].values())
    assert len(run.requests) == line["attempted"]
    assert all(r.done is not None for r in run.requests)


def test_streamed_matmul_at_16_bit_is_not_correct(tiny_matmul):
    _, line = _run(tiny_matmul, control=True)
    assert line["correct"] is False
    assert line["checks"]["mismatched"]["value"] == line["attempted"] > 0


def test_matmul_kernel_program_is_the_one_built_directly():
    from repro.kvi.programs import matmul_program
    from repro.kvi.workload import structural_signature
    config = spec.load_config("kvi-matmul64")
    got = spec.kernel(config["kernel"]).program(config,
                                                config["elem_bytes"])
    A = np.random.default_rng(0).integers(-128, 128, (64, 64)).astype(
        np.int32)
    want = matmul_program(A, np.zeros((64, 64), np.int32), shift=2,
                          resident=False, elem_bytes=4)
    assert got.meta["resident"] is False
    assert structural_signature(got) == structural_signature(want)
    assert sorted(got.mem_init) == sorted(want.mem_init)
    for k, v in want.mem_init.items():
        assert got.mem_init[k].dtype == v.dtype
        np.testing.assert_array_equal(got.mem_init[k], v)
    assert config["data_mems"] == [m.name for m in got.mems
                                   if m.name.startswith("bcol")]


def test_streamed_form_is_the_programs_own_choice_at_32_bit():
    from repro.kvi.programs import matmul_program
    z = np.zeros((64, 64), np.int32)
    # (64*64 + 2*64 + 64) * 4 = 17152 bytes > 16384
    assert matmul_program(z, z, elem_bytes=4).meta["resident"] is False
    assert matmul_program(z, z, elem_bytes=2).meta["resident"] is True


def _wrapped(x, shift):
    """Exact integers wrapped to 32-bit two's complement, then shifted."""
    w = (x + 2**31) % 2**32 - 2**31
    return np.vectorize(lambda v: int(v) >> shift)(w).astype(np.int64)


def test_reference_is_the_wrapped_product_shifted():
    rng = np.random.default_rng(2**33 + 5)
    A = rng.integers(-2**20, 2**20, (5, 7))
    B = rng.integers(-2**20, 2**20, (3, 7, 6))
    exact = np.stack([A.astype(object) @ b.astype(object) for b in B])
    want = _wrapped(exact, 3)
    assert (np.abs(exact) >= 2**31).any()          # the sums do wrap
    got = matmul.matmul(A, B, 3)
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got.astype(np.int64), want)


def test_reference_takes_the_columns_and_gives_the_rows():
    config = dict(spec.load_config("kvi-matmul64"), n=4, m=3, p=2,
                  shift=1)
    B = np.arange(2 * 3 * 2).reshape(2, 3, 2) - 5
    inputs = {f"bcol{j}": B[:, :, j] for j in range(2)}
    out = matmul.expected(config, inputs)
    A = matmul.weights(config).astype(np.int64)
    assert sorted(out) == [f"row{i}" for i in range(4)]
    for i in range(4):
        np.testing.assert_array_equal(out[f"row{i}"],
                                      (np.einsum("k,rkj->rj", A[i], B)
                                       >> 1))


def test_reference_equals_the_oracle_at_the_papers_size():
    from repro.kvi.backend import get_backend
    from repro.kvi.lowering import TraceCache
    from bench.runners.kvi_serve import build_template
    config = spec.load_config("kvi-matmul64")
    prog = build_template(config, TraceCache()).instantiate(2**40 + 3, 0)
    got = get_backend("oracle").run(prog).outputs
    inputs = {m.name: prog.mem_init[m.id][None] for m in prog.mems
              if m.name in config["data_mems"]}
    want = matmul.expected(config, inputs)
    assert set(want) == set(got)
    for k, w in want.items():
        np.testing.assert_array_equal(got[k], w[0])


def test_matmul64_hand_count():
    # 64^3 MACs, 2 ops each; A, B and C are 64x64, 4 bytes an element
    assert matmul.matmul_work(64, 64, 64, 4) == (524288, 49152)
    config = spec.load_config("kvi-matmul64")
    assert matmul.work(config) == (2 * 64**3, 3 * 64 * 64 * 4)


def _trace(top_ops, busy_s=0.5):
    return records.TraceSummary(window_s=2.0, busy_s=busy_s, n_ops=100,
                                top_ops=top_ops, idle_gaps=[])


def _run_record(trace):
    run = records.RunRecord(open_loop=False, setup_s=1.0, t_open=0.0)
    run.trace = trace
    return run


REDUCE = "custom-call:tpu_custom_call s32[8,128]"
REGION = "custom-call:tpu_custom_call (s32[8,128], s32[8,128])"
DUS = "dynamic-update-slice s32[8,64]"


def test_reader_finds_the_reduction_kernel():
    run = _run_record(_trace([[REDUCE, 0.3], [DUS, 0.1], [REGION, 0.05],
                              ["custom-call:tpu_custom_call s32[1,128]",
                               0.1]]))
    share = spec.reader("reduce_device_share")(run)
    assert share == pytest.approx(100.0 * 0.4 / 0.5)


@pytest.mark.parametrize("top_ops", [
    [], [[DUS, 0.4]], [[REGION, 0.4]],
    [["custom-call:tpu_custom_call s32[8,64]", 0.4]]],
    ids=["nothing", "dus", "region", "other_tile"])
def test_reader_gives_0_without_the_reduction_kernel(top_ops):
    run = _run_record(_trace(top_ops))
    assert spec.reader("reduce_device_share")(run) == 0.0


def test_reader_finds_nothing_without_a_trace():
    assert spec.reader("reduce_device_share")(_run_record(None)) is None
    idle = _run_record(_trace([[REDUCE, 0.0]], busy_s=0.0))
    assert spec.reader("reduce_device_share")(idle) is None
