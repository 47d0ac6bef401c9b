"""The control of ``correct``: the cell served at the program's own
next lower precision (``control_elem_bytes`` of its configuration:
16-bit for the 32-bit kernels), through the same timed path, compared
with the same 32-bit reference. Its readings must fail the limits.

    python -m bench.control --workload conv32.closed32 --seeds 1,2,3 \\
        --seconds 10

Prints one JSON line per seed with the numbers compared and ``correct``.
It needs the chip; ``tests/bench`` runs it at a small size on the CPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from bench import run as bench_run
from bench import spec


def control_point(bench, cell: str, seed: int, seconds: float,
                  device) -> dict:
    _, line = bench_run.execute(bench, cell, seed, seconds, False, device,
                                time.perf_counter(), control=True)
    return {"workload": cell, "seed": seed, "correct": line["correct"],
            "attempted": line["attempted"], "checks": line["checks"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    try:
        device = bench_run.device_info(spec.workload(bench, args.workload)
                                       ["chips"])
    except bench_run.NoChip as e:
        print(f"bench.control: {e}", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(control_point(bench, args.workload, seed,
                                       args.seconds, device)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
