"""Run one benchmark cell once on the chip and print its result line.

    python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration and a traffic mix; the configuration names its runner
kind. With ``--trace 0`` the line carries the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, read from a profiler
trace of the window. The last line of stdout is one JSON object;
the numbers compared for ``correct`` close stderr, each beside its
limit. The run fails, and prints no result, where JAX's first device is
not a TPU or there are fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, Mapping  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
if str(ROOT / "src") not in sys.path:
    sys.path.insert(1, str(ROOT / "src"))

from bench import spec  # noqa: E402
from bench.peaks import peak_for  # noqa: E402


class NoChip(RuntimeError):
    pass


def device_info(chips: int) -> Dict[str, object]:
    """JAX's devices, refused unless they are at least ``chips`` TPUs."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX's first device is on platform "
                     f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX sees "
                     f"{len(devices)}")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": chips}


def execute(bench: Mapping, cell: str, seed: int, seconds: float,
            trace: bool, device: Dict[str, object], t_start: float,
            base: Path = spec.BENCH_DIR, **runner_kw):
    """Run the cell once on whatever device JAX has; return the record
    and its result line."""
    w = spec.workload(bench, cell)
    config = spec.load_config(w["config"], base)
    traffic = spec.load_traffic(w["traffic"], base)
    peak = peak_for(str(device["kind"])) \
        if device.get("platform") == "tpu" else None
    run = spec.runner(config["runner"])(
        config, traffic, seed=seed, seconds=seconds, trace=trace,
        t_start=t_start, **runner_kw)
    run.peak = peak
    return run, result_line(bench, cell, run, device, trace)


def result_line(bench: Mapping, cell: str, run, device: Mapping,
                trace: bool) -> Dict[str, object]:
    """The run's last line: ``correct``, ``attempted``, ``failed``, the
    cell's metrics, the device, with ``--trace 1`` the breakdown, and
    the numbers compared for ``correct`` last, each beside its limit."""
    dev = dict(device, **run.device)
    line = {"correct": run.correct, "attempted": len(run.requests),
            "failed": run.failed,
            "metrics": spec.read_metrics(
                spec.metrics_for(bench, cell, trace), run),
            "device": dev}
    if trace and run.trace is not None:
        dev["busy_s"] = run.trace.busy_s
        dev["window_s"] = run.trace.window_s
        line["breakdown"] = {"device_ops": run.trace.top_ops,
                             "idle_gaps": run.trace.idle_gaps}
    line["checks"] = run.checks
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--records", default=None,
                    help="also write the per-request records as JSON here")
    args = ap.parse_args(argv)

    bench = spec.load_benchmark()
    w = spec.workload(bench, args.workload)
    try:
        device = device_info(w["chips"])
    except NoChip as e:
        print(f"bench.run: {e}", file=sys.stderr)
        return 2
    run, line = execute(bench, args.workload, args.seed, args.seconds,
                        bool(args.trace), device, _T_START)
    if args.records:
        write_records(run, args.records)
    print(f"setup phases: {json.dumps(run.setup_phases)}", file=sys.stderr)
    for name, c in run.checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


def write_records(run, path: str) -> None:
    """Per-request and per-batch times, relative to the window's
    opening, for looking at a run afterwards."""
    t0 = run.t_open
    rel = lambda t: None if t is None else t - t0  # noqa: E731
    data = {"requests": [[rel(r.due), rel(r.sent), rel(r.done), r.late]
                         for r in run.requests],
            "batches": [[rel(b.start), rel(b.end), b.size]
                        for b in run.batches],
            "steps": [[rel(s.start), rel(s.end), s.backend_s, s.buckets]
                      for s in run.steps],
            "setup_s": run.setup_s, "setup_phases": run.setup_phases,
            "compiles_in_window": run.compiles_in_window}
    Path(path).parent.mkdir(parents=True, exist_ok=True)
    with open(path, "w") as f:
        json.dump(data, f)


if __name__ == "__main__":
    sys.exit(main())
