"""Sweep a configuration under an open-loop traffic mix over fixed arrival
rates, to find its knee: the highest rate at which the backlog does not
grow.

    python -m bench.sweep --config kvi-conv32 --traffic open-conv32 \\
        --rates 8,12,16,20 --seconds 20 --seed 7 [--out sweep.jsonl]

Each rate runs the configuration once, in this process, under the
open-loop traffic mix at that rate, and prints one JSON line: arrivals,
completions by the window's close, the backlog then, how long the
backlog took to drain, the latency percentiles, and the median latency
of the requests due in each half of the window (a backlog that grows
shows as a second half slower than the first). It needs the chip.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from bench import run as bench_run
from bench import spec
from bench.stats import percentile


def sweep_point(config_name: str, traffic_name: str, rate: float,
                seconds: float, seed: int, device,
                base=spec.BENCH_DIR) -> dict:
    config = spec.load_config(config_name, base)
    traffic = dict(spec.load_traffic(traffic_name, base), rate_rps=rate)
    if traffic["loop"] != "open":
        raise ValueError(f"{traffic_name} is not an open-loop mix")
    rec = spec.runner(config["runner"])(
        config, traffic, seed=seed, seconds=seconds, trace=False,
        t_start=time.perf_counter())
    close = rec.t_open + seconds
    done = [r.done for r in rec.requests if r.done is not None]
    lat = rec.latencies_s
    due_by_close = sum(1 for r in rec.requests if r.due <= close)
    half = rec.t_open + seconds / 2
    first = [r.done - r.due for r in rec.requests
             if r.done is not None and r.due < half]
    second = [r.done - r.due for r in rec.requests
              if r.done is not None and r.due >= half]
    done_by_close = sum(1 for d in done if d <= close)
    return {"rate_rps": rate, "arrivals": len(rec.requests),
            "completed_by_close": done_by_close,
            "backlog_at_close": due_by_close - done_by_close,
            "drain_s": max(done) - close if done else None,
            "latency_p50_ms": 1e3 * percentile(lat, 50) if lat else None,
            "latency_p95_ms": 1e3 * percentile(lat, 95) if lat else None,
            "p50_first_half_ms": 1e3 * percentile(first, 50) if first else None,
            "p50_second_half_ms":
                1e3 * percentile(second, 50) if second else None,
            "correct": rec.correct, "device": device}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated arrival rates, requests/s")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    try:
        device = bench_run.device_info(1)
    except bench_run.NoChip as e:
        print(f"bench.sweep: {e}", file=sys.stderr)
        return 2
    for rate in (float(r) for r in args.rates.split(",")):
        point = sweep_point(args.config, args.traffic, rate, args.seconds,
                            args.seed, device)
        print(json.dumps(point), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(point) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
