"""Smoke run of the served paths on one TPU, in one process.

    python chip_smoke.py

1. KVI serving: a Poisson stream of ``DEFAULT_MIX`` requests at the
   paper's sizes (conv 32x32, FFT-256, resident matmul 64x64) through
   ``ServeEngine`` + ``PallasBackend`` — the path behind
   ``python -m repro.kvi.serving`` — with compiled Mosaic kernels. Checks:
   no compile inside the serving loop after prewarm; every template at
   every batch bucket bit-identical to the numpy oracle; a cached kernel's
   compiled HLO holds a ``tpu_custom_call``.
2. LM serving: 4 requests on ``llama3.2-1b`` at published width through
   ``repro.launch.serve`` with seeded random weights, twice. Checks: the
   engine saw finite logits at every step, and both runs give the same
   greedy tokens.

Counts, compile seconds and compile counts (from JAX's monitoring events)
and wall times go to the earlier lines of stdout; the last line is one
JSON object naming the device. When JAX's first device is not a TPU the
script exits non-zero before any phase and prints no result.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

N_REQUESTS = 32
MAX_BATCH = 8
SEED = 0
LM_ARCH = "llama3.2-1b"
_TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration")
# wraps the persistent-cache lookup and write as well as the compile
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileStats:
    """XLA compiles (Mosaic kernels included) counted from JAX's
    monitoring events."""

    def __init__(self):
        import jax
        self.count, self.compile_s, self.trace_s = 0, 0.0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_) -> None:
        if event == _COMPILE_EVENT:
            self.count += 1
            self.compile_s += secs
        elif event in _TRACE_EVENTS:
            self.trace_s += secs

    def snapshot(self) -> tuple:
        return self.count, self.compile_s, self.trace_s


def device_info() -> dict:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX's first device is on "
                 f"platform {devices[0].platform!r}")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def phase_kvi(smoke: bool = False) -> None:
    """``smoke`` swaps in the small templates for a rehearsal on the CPU."""
    import jax
    import numpy as np
    from repro.kvi.backend import get_backend
    from repro.kvi.passes.fusion import META_KEY
    from repro.kvi.serving import (DEFAULT_MIX, ServeEngine, make_templates,
                                   poisson_arrivals)
    from repro.kvi.workload import KviWorkload

    templates = make_templates(DEFAULT_MIX, smoke=smoke, seed=SEED)
    specs = poisson_arrivals(templates, N_REQUESTS, 40.0, seed=SEED)
    backend = get_backend("pallas", passes=())
    engine = ServeEngine(templates, backend=backend, max_batch=MAX_BATCH,
                         seed=SEED)
    rep = engine.run(specs)
    cc, tp = rep["compile_cache"], rep["throughput"]
    print(f"kvi: {tp['requests']} requests over {rep['n_steps']} steps, "
          f"buckets {rep['batch_sizes']} (max_batch={MAX_BATCH}), "
          f"{tp['pallas_calls']} pallas_calls "
          f"({tp['pallas_calls_per_request']}/request)")
    print(f"kvi: prewarm compiled {cc['misses']} kernels in "
          f"{tp['prewarm_s']} s; serving loop {tp['execute_s']} s with "
          f"{cc['loop_misses']} compiles and {cc['hits']} cache hits")
    for name in sorted(templates):
        t = templates[name]
        print(f"kvi: template {name}: {t.program.n_instructions} "
              f"instructions, {len(t.program.meta[META_KEY].regions)} "
              f"fused regions")
    if cc["loop_misses"]:
        raise AssertionError(f"{cc['loop_misses']} compiles inside the "
                             f"serving loop after prewarm")

    oracle = get_backend("oracle")
    buckets = [2 ** i for i in range(MAX_BATCH.bit_length())]
    before = backend.kernel_cache.misses
    checked = 0
    for name in sorted(templates):
        tpl = templates[name]
        for size in buckets:
            progs = [tpl.instantiate(SEED, 10_000 + 100 * size + i)
                     for i in range(size)]
            res = backend.run_workload(KviWorkload.homogeneous(progs))
            for prog, got in zip(progs, res.entry_results):
                want = oracle.run(prog)
                for k, w in want.outputs.items():
                    if not np.array_equal(w, got.outputs[k]):
                        raise AssertionError(
                            f"{name} bucket {size}: output {k!r} differs "
                            f"from the oracle")
                checked += 1
    if backend.kernel_cache.misses != before:
        raise AssertionError("oracle check compiled outside the prewarm")
    print(f"kvi: {checked} requests over {len(templates)} templates x "
          f"buckets {buckets} bit-identical to the oracle")

    key, fn = next((k, f) for k, f in backend.kernel_cache.items()
                   if k[0] == "fused")
    _, _, in_slots, _, _, N, n, _, dt = key
    hlo = fn.lower(*[jax.ShapeDtypeStruct((N, n), dt)] * len(in_slots)
                   ).compile().as_text()
    if "tpu_custom_call" not in hlo:
        raise AssertionError("cached fused kernel compiled without a "
                             "Mosaic tpu_custom_call")
    print(f"kvi: cached fused kernel (N={N}, n={n}, {dt}) compiles to a "
          f"tpu_custom_call")


def phase_lm(reduced: bool = False) -> None:
    """``reduced`` swaps in the tiny preset for a rehearsal on the CPU."""
    from repro.launch import serve
    argv = ["--arch", LM_ARCH, "--requests", "4", "--slots", "4",
            "--max-seq", "128", "--max-new", "16", "--seed", str(SEED)]
    if reduced:
        argv.append("--reduced")
    runs = []
    for i in range(2):
        t0 = time.perf_counter()
        done = serve.run(argv)
        print(f"lm: run {i} served {len(done)} requests in "
              f"{time.perf_counter() - t0:.2f} s (compile included)")
        runs.append({r.rid: list(r.out_tokens) for r in done})
    if len(runs[0]) != 4 or any(len(t) != 16 for t in runs[0].values()):
        raise AssertionError(f"lm: unexpected outputs {runs[0]}")
    if runs[0] != runs[1]:
        raise AssertionError("lm: greedy tokens differ between two runs")
    print(f"lm: {LM_ARCH} logits finite at every step; greedy tokens "
          f"identical across two runs: {runs[0][0]}")


def main() -> int:
    device = device_info()
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.runtime.compile_cache import enable_compile_cache
    import jax
    print(f"device: {device}; compile cache: {enable_compile_cache()}, "
          f"keeping compiles over "
          f"{jax.config.jax_persistent_cache_min_compile_time_secs} s")
    stats = CompileStats()
    for phase in (phase_kvi, phase_lm):
        t0 = time.perf_counter()
        n0, c0, l0 = stats.snapshot()
        phase()
        n1, c1, l1 = stats.snapshot()
        print(f"{phase.__name__}: passed in {time.perf_counter() - t0:.2f} s; "
              f"{n1 - n0} XLA compiles took {c1 - c0:.2f} s (persistent "
              f"cache reads and writes included), tracing and lowering "
              f"{l1 - l0:.2f} s", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
