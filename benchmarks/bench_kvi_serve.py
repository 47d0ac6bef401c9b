"""Serving-under-load benchmark: the KVI serving engine's headline
numbers, emitted to ``BENCH_kvi_serve.json``.

One Poisson request stream (mixed kernels, mixed precisions, ~1000
simulated clients) is served three times:

  * batched, twice — signature batching + prewarmed kernel cache, run
    two times from scratch to prove the canonical report (wall-clock
    fields scrubbed) is byte-identical under the seed;
  * unbatched once — the same schedule executed one request at a time,
    the baseline the batching gate compares dispatch counts against.

Gates (the harness and CI fail when any is False):

  * ``deterministic``        — canonical reports byte-identical
  * ``steady_hit_rate_1``    — zero compiles inside the serving loop
                               (prewarming covered every batch shape)
  * ``batched_fewer_pallas_calls`` — batching issues fewer
                               ``pallas_call``s per request than the
                               one-at-a-time baseline (a count: wall
                               times here are not device numbers)
  * ``outputs_match_oracle`` — batched execution is bit-identical to
                               the scalar oracle on sampled requests

Run:  PYTHONPATH=src python -m benchmarks.bench_kvi_serve [--smoke]
or through the harness:  python -m benchmarks.run --only kvi_serve
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np


def _engine(templates, backend, batching: bool, seed: int):
    from repro.kvi.serving import ServeEngine
    return ServeEngine(templates, n_harts=3, backend=backend,
                       batching=batching, max_batch=8, seed=seed)


def _oracle_check(templates, seed: int, per_template: int = 3) -> bool:
    """Batched Pallas execution vs the scalar oracle, bit for bit, on a
    sample of instantiated requests per template."""
    from repro.kvi.backend import get_backend
    from repro.kvi.workload import KviWorkload
    oracle = get_backend("oracle")
    pallas = get_backend("pallas", passes=())
    for name in sorted(templates):
        tpl = templates[name]
        progs = [tpl.instantiate(seed, 10_000 + i)
                 for i in range(per_template)]
        batched = pallas.run_workload(
            KviWorkload.homogeneous(progs, name=f"check.{name}"))
        for prog, got in zip(progs, batched.entry_results):
            want = oracle.run(prog)
            for k in want.outputs:
                if not np.array_equal(want.outputs[k], got.outputs[k]):
                    return False
    return True


def run(emit, seed: int = 0, smoke: bool = True) -> dict:
    from repro.kvi.backend import get_backend
    from repro.kvi.serving import (DEFAULT_MIX, SMOKE_MIX,
                                   canonical_report, make_templates,
                                   poisson_arrivals)

    mix = SMOKE_MIX if smoke else DEFAULT_MIX
    n_requests = 32 if smoke else 96
    templates = make_templates(mix, smoke=smoke, seed=seed)
    specs = poisson_arrivals(templates, n_requests,
                             mean_interarrival_cycles=80.0,
                             n_clients=1000, seed=seed)
    emit(f"# mix={sorted(templates)} requests={len(specs)} "
         f"clients={len({s.client for s in specs})}")

    emit("# --- batched serve, run A (fresh backend) ---")
    rep_a = _engine(templates, get_backend("pallas", passes=()),
                    True, seed).run(specs)
    emit("# --- batched serve, run B (fresh backend) ---")
    rep_b = _engine(templates, get_backend("pallas", passes=()),
                    True, seed).run(specs)
    deterministic = canonical_report(rep_a) == canonical_report(rep_b)

    emit("# --- unbatched baseline (one request per dispatch) ---")
    rep_u = _engine(templates, get_backend("pallas", passes=()),
                    False, seed).run(specs)

    batched_calls = rep_a["throughput"]["pallas_calls_per_request"]
    unbatched_calls = rep_u["throughput"]["pallas_calls_per_request"]
    cc = rep_a["compile_cache"]
    lat = rep_a["latency_cycles"]
    emit(f"# pallas_calls/request batched {batched_calls} vs unbatched "
         f"{unbatched_calls}; loop misses={cc['loop_misses']} "
         f"(steady hit rate {cc['steady_hit_rate']}); "
         f"p50={lat['p50']} p95={lat['p95']} p99={lat['p99']} cycles")

    outputs_ok = _oracle_check(templates, seed)
    emit(f"# outputs_match_oracle={outputs_ok} "
         f"deterministic={deterministic}")

    return {
        "seed": seed,
        "smoke": smoke,
        "serve": rep_a,
        "unbatched": {
            "throughput": rep_u["throughput"],
            "batch_sizes": rep_u["batch_sizes"],
        },
        "checks": {
            "deterministic": deterministic,
            "steady_hit_rate_1": cc["steady_hit_rate"] == 1.0,
            "batched_fewer_pallas_calls":
                0 < batched_calls < unbatched_calls,
            "outputs_match_oracle": outputs_ok,
        },
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small kernels + short stream (CI-sized)")
    ap.add_argument("--seed", type=int, default=0,
                    help="load + data seed (reproducible stream)")
    ap.add_argument("--out", default="BENCH_kvi_serve.json")
    args = ap.parse_args(argv)
    result = run(emit=print, seed=args.seed, smoke=args.smoke)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
    print(f"# wrote {args.out}")
    gates = {k: v for k, v in result["checks"].items()
             if isinstance(v, bool)}
    if not all(gates.values()):
        print(f"# FAILED gates: "
              f"{sorted(k for k, v in gates.items() if not v)}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
