"""The harness end to end on the CPU at a small size, past its look for
a chip: the result line, the control and the faults that ``correct``
must catch, discovery by file name, and the refusal of the CPU."""
import json
import sys

import numpy as np
import pytest

from _perfbench_helpers import (CPU, ROOT, no_compile_cache,  # noqa: F401
                                run_small, small_bench)
from bench import records, run as bench_run, spec

pytestmark = pytest.mark.usefixtures("no_compile_cache")


def test_the_cpu_is_refused_and_no_result_printed(capsys):
    rc = bench_run.main(["--workload", "conv32.closed32", "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out == ""
    assert "needs a TPU" in err


def test_device_check_refuses_fewer_chips_than_asked(monkeypatch):
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"
    import jax
    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    assert bench_run.device_info(1)["kind"] == "TPU v5 lite"
    with pytest.raises(bench_run.NoChip, match="4 chips"):
        bench_run.device_info(4)


def test_a_directory_with_only_the_benchmark_fails(tmp_path):
    import shutil
    import subprocess
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "conv32.closed32",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": str(tmp_path)})
    assert p.returncode != 0
    assert p.stdout == ""


@pytest.mark.parametrize("cell", ["tiny.conv.closed", "tiny.conv.open",
                                  "tiny.fft.closed"])
def test_small_cell_is_correct_and_its_line_has_the_schema(cell):
    run, line = run_small(cell)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"latency_p50_ms", "latency_p95_ms",
                                    "throughput_rps", "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and set(m) == {"value", "unit"}
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in line["checks"].values())
    json.dumps(line)
    # every request of the window was compared
    assert len(run.requests) == line["attempted"]
    assert all(r.done is not None for r in run.requests)
    if cell == "tiny.conv.open":
        assert all(r.late is not None and r.late >= 0 for r in run.requests)


def test_per_layer_line_from_a_traced_record():
    _, untraced = run_small("tiny.conv.open")
    run, _ = run_small("tiny.conv.open")
    from bench.peaks import peak_for
    run.peak = peak_for("TPU v5 lite")
    run.trace = records.TraceSummary(
        window_s=2.0, busy_s=0.5, n_ops=40,
        top_ops=[["fusion", 0.3]], idle_gaps=[["engine.run", 0.2]])
    line = bench_run.result_line(small_bench(), "tiny.conv.open", run,
                                 dict(CPU), trace=True)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "checks"]
    assert line["device"]["busy_s"] == 0.5
    assert line["device"]["window_s"] == 2.0
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(m) == {s["name"] for s in small_bench()["per_layer"]}
    assert m["device_idle_share"] == pytest.approx(75.0)
    assert m["device_ops_per_batch"] == pytest.approx(40 / len(run.batches))
    assert 0 < m["kernels_roofline"] <= 100
    assert m["compiles_in_window"] == 0
    assert 1 <= m["batch_size_mean"] <= 8
    assert line["breakdown"]["idle_gaps"] == [["engine.run", 0.2]]
    assert set(untraced["metrics"]) == {"latency_p50_ms", "latency_p95_ms",
                                        "throughput_rps", "setup_s"}


def test_readers_leave_out_what_a_run_cannot_read():
    run, _ = run_small("tiny.conv.closed")
    for name in ("kernels_roofline", "device_idle_share",
                 "device_ops_per_batch"):
        assert spec.reader(name)(run) is None                 # no trace


def test_control_at_lower_precision_is_not_correct():
    from bench import control
    for cell in ("tiny.conv.closed", "tiny.fft.closed"):
        run, line = run_small(cell, control=True)
        assert line["correct"] is False
        assert line["checks"]["mismatched"]["value"] == line["attempted"]
    assert callable(control.control_point)


def _faulty(monkeypatch, fault):
    """Break ``PallasBackend.run_workload`` underneath the timed path."""
    from repro.kvi.backend import BackendResult
    from repro.kvi.pallas_backend import PallasBackend
    from repro.kvi.workload import WorkloadResult
    real = PallasBackend.run_workload

    def broken(self, workload, verify=None):
        res = real(self, workload, verify=verify)
        outs = [{k: np.array(v) for k, v in o.items()} for o in res.outputs]
        if fault == "answer_altered":
            k = sorted(outs[0])[0]
            outs[0][k].reshape(-1)[0] += 1
        elif fault == "half_batch_left_out":
            outs = outs[:(len(outs) + 1) // 2]
        elif fault == "state_unchanged":
            outs = [{m.name: np.array(e.program.mem_init[m.id])
                     for m in e.program.outputs} for e in workload.entries]
        return WorkloadResult(res.backend, res.workload,
                              tuple(BackendResult(res.backend, o)
                                    for o in outs), meta=res.meta)
    monkeypatch.setattr(PallasBackend, "run_workload", broken)


@pytest.mark.parametrize("fault,check", [
    ("answer_altered", "mismatched"),
    ("half_batch_left_out", "unanswered"),
    ("state_unchanged", "mismatched")])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault, check):
    _faulty(monkeypatch, fault)
    _, line = run_small("tiny.conv.closed")
    assert line["correct"] is False
    assert line["checks"][check]["value"] > line["checks"][check]["limit"]


def test_configs_mixes_metrics_and_runners_are_found_by_file(tmp_path,
                                                             monkeypatch):
    import bench.metrics
    import bench.runners
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "configs" / "new-cfg.json").write_text(
        json.dumps({"runner": "echo_kind", "size": 3}))
    (tmp_path / "traffic" / "new-mix.json").write_text(
        json.dumps({"loop": "closed", "clients": 2}))
    (tmp_path / "new_metric.py").write_text(
        "def read(run):\n    return 7.0 * len(run.requests)\n")
    (tmp_path / "echo_kind.py").write_text(
        "from bench.records import Request, RunRecord\n"
        "def run(config, traffic, **kw):\n"
        "    r = RunRecord(open_loop=False, setup_s=1.0, t_open=0.0,\n"
        "                  requests=[Request(0.0, 0.0, 1.0)] * config['size'])\n"
        "    r.correct = True\n"
        "    return r\n")
    monkeypatch.setattr(bench.metrics, "__path__",
                        list(bench.metrics.__path__) + [str(tmp_path)])
    monkeypatch.setattr(bench.runners, "__path__",
                        list(bench.runners.__path__) + [str(tmp_path)])
    bench_spec = small_bench()
    bench_spec["workloads"].append({"name": "new.cell", "config": "new-cfg",
                                    "traffic": "new-mix", "chips": 1})
    bench_spec["per_layer"].append({"name": "new_metric", "unit": "x",
                                    "workloads": ["new.cell"]})
    run, line = bench_run.execute(bench_spec, "new.cell", 1, 1.0, True, CPU,
                                  0.0, base=tmp_path)
    assert line["correct"] is True and line["attempted"] == 3
    assert line["metrics"]["new_metric"] == {"value": 21.0, "unit": "x"}
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert "new_metric" not in bench_run.result_line(
        bench_spec, "tiny.conv.closed", run, CPU, True)["metrics"]


def test_kernels_are_found_by_file(tmp_path, monkeypatch):
    """A kernel that is only a file outside ``bench/kernels``, named only
    by a configuration outside ``bench/configs``, is served and checked."""
    import bench.kernels
    from _perfbench_helpers import DATA
    (tmp_path / "kernels").mkdir()
    (tmp_path / "kernels" / "conv_twin.py").write_text(
        "from bench.kernels.conv import expected, program, work  # noqa\n")
    config = dict(spec.load_config("tiny-conv", DATA), name="tiny-twin",
                  kernel="conv_twin")
    for kind, name, data in (("configs", "tiny-twin", config),
                             ("traffic", "closed4",
                              spec.load_traffic("closed4", DATA))):
        (tmp_path / kind).mkdir()
        (tmp_path / kind / f"{name}.json").write_text(json.dumps(data))
    monkeypatch.setattr(bench.kernels, "__path__",
                        list(bench.kernels.__path__)
                        + [str(tmp_path / "kernels")])
    bench_spec = small_bench()
    bench_spec["workloads"].append({"name": "tiny.twin.closed",
                                    "config": "tiny-twin",
                                    "traffic": "closed4", "chips": 1})
    _, line = bench_run.execute(bench_spec, "tiny.twin.closed", 2**31 + 29,
                                0.5, False, CPU, 0.0, base=tmp_path)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0


def test_names_outside_the_alphabet_are_refused():
    for bad in ("../x", "a b", "a/b", ""):
        with pytest.raises(ValueError):
            spec.load_config(bad)


def test_every_cell_of_the_benchmark_resolves():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        config = spec.load_config(w["config"])
        spec.load_traffic(w["traffic"])
        assert callable(spec.runner(config["runner"]))
        kernel = spec.kernel(config["kernel"])
        assert all(callable(getattr(kernel, f))
                   for f in ("program", "expected", "work"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.reader(m["name"]))


def test_sweep_point_reports_arrivals_backlog_and_latency():
    from _perfbench_helpers import DATA
    from bench.sweep import sweep_point
    point = sweep_point("tiny-conv", "open40", 30.0, 0.5, 7, CPU, base=DATA)
    assert point["arrivals"] == 15 and point["correct"] is True
    assert 0 <= point["completed_by_close"] <= point["arrivals"]
    assert point["backlog_at_close"] >= 0
    assert point["latency_p95_ms"] >= point["latency_p50_ms"] > 0
