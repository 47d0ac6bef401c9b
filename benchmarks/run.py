"""Benchmark harness: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only table2,fig2,...]
                                          [--seed N]

Prints each benchmark's detailed report, then a final
``name,us_per_call,derived`` CSV summary (us_per_call = harness wall time
per benchmark; derived = that benchmark's headline check).

``--seed`` is forwarded to every benchmark whose ``run()`` accepts a
``seed`` keyword, so the randomized inputs behind the BENCH_*.json
artifacts are reproducible run-to-run. Giving ``--seed`` while
selecting a benchmark that does *not* accept one is an error naming
that benchmark — the flag is never silently dropped — and the check
runs for every selected benchmark up front, before any of them start.
"""
from __future__ import annotations

import argparse
import inspect
import sys
import time


def bench_kwargs(name: str, mod, seed) -> dict:
    """Keyword arguments to forward to ``mod.run`` for bench ``name``.

    ``seed is None`` (flag not given) forwards nothing — seed-aware
    benches fall back to their own reproducible default. An explicit
    seed is forwarded only to a ``run()`` that declares the keyword;
    otherwise raise, naming the bench, so a typo'd ``--only`` +
    ``--seed`` combination fails loudly instead of silently measuring
    unseeded inputs."""
    if seed is None:
        return {}
    params = inspect.signature(mod.run).parameters
    if "seed" not in params:
        raise SystemExit(
            f"benchmarks.run: --seed {seed} given, but benchmark "
            f"{name!r} ({mod.__name__}.run) does not accept a 'seed' "
            f"keyword — it would be silently dropped. Re-run without "
            f"--seed, or restrict --only to seed-aware benchmarks.")
    return {"seed": seed}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma list: table2,fig2,fig3,fig4,table3,kernels,"
                         "roofline,kvi_batch,kvi_passes,kvi_dse,"
                         "kvi_search,kvi_serve")
    ap.add_argument("--seed", type=int, default=None,
                    help="input-data seed, forwarded to seed-aware "
                         "benchmarks (error if a selected benchmark "
                         "cannot accept it)")
    args = ap.parse_args(argv)

    from benchmarks import (bench_kvi_batch, bench_kvi_dse, bench_kvi_passes,
                            bench_kvi_search, bench_kvi_serve, fig2_dlp_tlp,
                            fig3_exec_time, fig4_energy, kernel_micro,
                            roofline_report, table2_cycles, table3_filters)
    benches = {
        "table2": (table2_cycles,
                   lambda r: f"geomean_fit={r['checks']['fit_geomean_ratio']:.2f}"),
        "fig2": (fig2_dlp_tlp,
                 lambda r: f"combined_beats_dlp={r['checks']['combined_beats_dlp']}"),
        "fig3": (fig3_exec_time,
                 lambda r: f"conv32_speedup={r['checks']['conv32_speedup_max']:.1f}x"),
        "fig4": (fig4_energy,
                 lambda r: f"best_saving={r['checks']['best_saving_pct']:.0f}%"),
        "table3": (table3_filters,
                   lambda r: f"f11_speedup={r['checks']['speedup_f11']:.1f}x"),
        "kernels": (kernel_micro, lambda r: f"n_kernels={len(r)}"),
        "roofline": (roofline_report,
                     lambda r: f"cells={len(r['rows'])}"),
        "kvi_batch": (bench_kvi_batch,
                      lambda r: "batched_fewer_dispatches="
                      f"{r['checks']['batched_fewer_dispatches']},"
                      "sim_speedup="
                      f"{r['sim_perf']['speedup']}x"),
        "kvi_passes": (bench_kvi_passes,
                       lambda r: "cyclesim_reduced="
                       f"{r['checks']['cyclesim_reduced']},"
                       "pallas_calls_reduced="
                       f"{r['checks']['pallas_calls_reduced']}"),
        "kvi_dse": (bench_kvi_dse,
                    lambda r: "pareto_ordering_ok="
                    f"{r['checks']['pareto_ordering_ok']},"
                    "subword_2x="
                    f"{r['checks']['subword_2x_on_mfu_bound']}"),
        "kvi_search": (bench_kvi_search,
                       lambda r: "front_recovered="
                       f"{r['checks']['front_recovered']},"
                       "within_half_budget="
                       f"{r['checks']['within_half_budget']},"
                       "deterministic="
                       f"{r['checks']['deterministic']}"),
        "kvi_serve": (bench_kvi_serve,
                      lambda r: "batched_fewer_pallas_calls="
                      f"{r['checks']['batched_fewer_pallas_calls']},"
                      "steady_hit_rate_1="
                      f"{r['checks']['steady_hit_rate_1']},"
                      "deterministic="
                      f"{r['checks']['deterministic']}"),
    }
    only = [s for s in args.only.split(",") if s]
    unknown = [s for s in only if s not in benches]
    if unknown:
        raise SystemExit(f"benchmarks.run: unknown benchmark(s) "
                         f"{unknown} in --only; available: "
                         f"{', '.join(benches)}")
    selected = [(name, mod, derive)
                for name, (mod, derive) in benches.items()
                if not only or name in only]
    # validate the seed forwarding for EVERY selected bench before any
    # of them run — a late failure would waste the finished ones
    all_kwargs = {name: bench_kwargs(name, mod, args.seed)
                  for name, mod, _ in selected}
    rows = []
    for name, mod, derive in selected:
        print(f"\n================ {name} ================", flush=True)
        t0 = time.perf_counter()
        try:
            result = mod.run(emit=print, **all_kwargs[name])
            derived = derive(result)
        except Exception as e:  # noqa: BLE001 — report but keep harness alive
            derived = f"ERROR:{type(e).__name__}:{e}"
        us = (time.perf_counter() - t0) * 1e6
        rows.append((name, us, derived))
    print("\n# name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.0f},{derived}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
