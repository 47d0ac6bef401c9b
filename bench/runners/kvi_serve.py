"""Runner kind ``kvi_serve``: requests for one of the paper's kernels,
served through ``ServeEngine`` over ``PallasBackend`` from a wall-clock
client loop.

Each wave is the set of requests pending when the loop hands them over;
it gets an engine of its own (``prewarm=False``, its data seed drawn
from the run's seed and the wave's index), and all engines share one
backend, whose ``KernelCache`` holds the warm kernels, and one
``TraceCache``. The backend handed to the engines is
:class:`RecordingBackend`, which times each batch and keeps every
request's inputs and outputs, because ``ServeEngine.run`` returns none.
After the window every request of the window is compared with the plain
reference of the configuration's kernel (``bench/kernels/<kernel>.py``),
which also gives the request template's program and its work.
"""
from __future__ import annotations

import shutil
import time
from typing import Dict, List, Mapping, Optional

import numpy as np

from bench import spec, tracing
from bench.records import Batch, Request, RunRecord, Step
from bench.traffic import is_open, open_arrivals, sub_seed

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: an open loop stops sending this long after the window's close; what
#: is still unsent then is never answered
DRAIN_LIMIT_S = 60.0
_compiles = [0]
_listening = [False]


def _count_compiles() -> None:
    import jax
    if not _listening[0]:
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, secs, **_: _compiles.__setitem__(
                0, _compiles[0] + (event == _COMPILE_EVENT)))
        _listening[0] = True


def _enable_cache() -> None:
    """JAX's persistent cache in the checkout (``enable_compile_cache``),
    keeping every compile, however short: where the host sets a size
    limit on the cache, JAX keeps only compiles over a second, and every
    run would compile its kernels again."""
    import jax
    from repro.runtime.compile_cache import enable_compile_cache
    jax.config.update("jax_compilation_cache_max_size", -1)
    enable_compile_cache()


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class RecordingBackend:
    """Forwards ``run_workload`` to the backend under test, times each
    call on the host clock, and keeps each request's inputs and outputs
    by program name until the client loop takes them."""

    def __init__(self, inner, data_mems):
        self.inner = inner
        self.name = inner.name
        self.data_mems = frozenset(data_mems)
        self.batches: List[Batch] = []
        self.backend_s = 0.0
        self.served: Dict[str, tuple] = {}

    @property
    def kernel_cache(self):
        return self.inner.kernel_cache

    def run_workload(self, workload, verify=None):
        t0 = time.perf_counter()
        with _annotate("backend.run_workload"):
            res = self.inner.run_workload(workload, verify=verify)
        t1 = time.perf_counter()
        self.backend_s += t1 - t0
        self.batches.append(Batch(t0, t1, len(workload.entries)))
        for e, r in zip(workload.entries, res.entry_results):
            p = e.program
            inputs = {m.name: p.mem_init[m.id] for m in p.mems
                      if m.name in self.data_mems}
            self.served[p.name] = (inputs, r.outputs, t1)
        return res

    def take(self) -> Dict[str, tuple]:
        served, self.served = self.served, {}
        return served


def build_template(config: Mapping, trace_cache, control: bool = False):
    """The configuration's request template, built, optimized and
    profiled as the serving path does it. ``control`` builds it at the
    program's own lower precision (``control_elem_bytes``)."""
    from repro.kvi.passes import PassPipeline
    from repro.kvi.scheduler import simulated_profile
    from repro.kvi.serving import KernelTemplate, template_key

    kernel = config["kernel"]
    eb = config["control_elem_bytes"] if control else config["elem_bytes"]
    prog = PassPipeline.from_spec(None).run(
        spec.kernel(kernel).program(config, eb))
    profile = simulated_profile(prog, None, trace_cache=trace_cache)
    return KernelTemplate(template_key(kernel, eb), kernel, eb, prog,
                          frozenset(config["data_mems"]), profile,
                          data_limit=config["data_limit"])


def check(config: Mapping, requests: List[Request],
          served: List[Optional[tuple]]) -> Dict[str, Dict]:
    """Every request of the window against the reference: how many never
    got an answer, how many answers differ, and the widest difference.
    The comparison is exact, so each limit is 0."""
    got = [s for s in served if s is not None]
    mismatched, widest = 0, 0
    if got:
        names = sorted(got[0][0])
        inputs = {n: np.stack([np.asarray(g[0][n]) for g in got])
                  for n in names}
        want = spec.kernel(config["kernel"]).expected(config, inputs)
        for i, g in enumerate(got):
            diff = 0
            for name, w in want.items():
                out = np.asarray(g[1][name]).astype(np.int64).reshape(-1)
                diff = max(diff, int(np.max(np.abs(
                    out - w[i].astype(np.int64).reshape(-1)))))
            mismatched += diff > 0
            widest = max(widest, diff)
    return {"unanswered": {"value": len(served) - len(got), "limit": 0},
            "mismatched": {"value": mismatched, "limit": 0},
            "max_abs_diff": {"value": widest, "limit": 0}}


def run(config: Mapping, traffic: Mapping, *, seed: int, seconds: float,
        trace: bool, t_start: float, control: bool = False) -> RunRecord:
    """Set up, run the window, check every answer; see the module's
    docstring. ``t_start`` is when the process started (host clock).
    ``control`` serves the configuration at the program's own lower
    precision, against the same reference."""
    from repro.kvi.backend import get_backend
    from repro.kvi.lowering import TraceCache
    from repro.kvi.serving import RequestSpec, ServeEngine, bucket_sizes
    from repro.kvi.workload import KviWorkload

    phases = _Phases(t_start)
    _enable_cache()
    _count_compiles()
    phases.mark("start")
    max_batch, n_harts = config["max_batch"], config["harts"]
    open_loop = is_open(traffic)
    tc = TraceCache()
    tpl = build_template(config, tc, control=control)
    inner = get_backend("pallas", passes=())
    rec = RecordingBackend(inner, config["data_mems"])
    ops, nbytes = spec.kernel(config["kernel"]).work(config)
    phases.mark("template")

    # prewarm the bucket sizes this traffic produces, and no others
    if open_loop:
        sizes = [1 << i for i in range(max_batch.bit_length())]
        warm_wave = 2 * max_batch - 1
    else:
        sizes = sorted(set(bucket_sizes(traffic["clients"], max_batch)))
        warm_wave = traffic["clients"]
    for k, size in enumerate(sizes):
        progs = [tpl.instantiate(sub_seed(seed, "prewarm", k), i)
                 for i in range(size)]
        inner.run_workload(KviWorkload.homogeneous(progs))
    phases.mark("prewarm")

    def serve(n: int, wave_seed: int):
        specs = [RequestSpec(0, tpl.kernel, tpl.elem_bytes, client=i)
                 for i in range(n)]
        engine = ServeEngine({tpl.name: tpl}, n_harts=n_harts, backend=rec,
                             max_batch=max_batch, seed=wave_seed,
                             prewarm=False, trace_cache=tc)
        b0 = rec.backend_s
        t0 = time.perf_counter()
        with _annotate("engine.run"):
            engine.run(specs)
        t1 = time.perf_counter()
        step = Step(t0, t1, rec.backend_s - b0,
                    [b for s in engine.steps for b in s.buckets])
        served = rec.take()
        return step, [served.get(f"{tpl.name}#{i}") for i in range(n)]

    for w in range(traffic["warm_waves"]):
        serve(warm_wave, sub_seed(seed, "warm", w))
    rec.batches.clear()
    phases.mark("warm_waves")

    trace_dir = str(tracing.DEFAULT_DIR)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracing.start(trace_dir)
    compiles0 = _compiles[0]
    with _annotate(tracing.WINDOW_SPAN):
        t_open = time.perf_counter()
        if open_loop:
            due = [t_open + t for t in open_arrivals(traffic, seed, seconds)]
            requests, served, steps = _open_window(
                serve, due, seed, t_open + seconds + DRAIN_LIMIT_S)
        else:
            requests, served, steps = _closed_window(
                serve, traffic["clients"], seed, t_open + seconds)
    compiles = _compiles[0] - compiles0
    for r in requests:
        r.ops, r.nbytes = ops, nbytes

    summary = None
    if trace:
        tracing.stop()
        summary = tracing.summarize(tracing.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)

    record = RunRecord(open_loop=open_loop, setup_s=t_open - t_start,
                       t_open=t_open, requests=requests,
                       batches=list(rec.batches), steps=steps,
                       compiles_in_window=compiles, trace=summary,
                       setup_phases=phases.spans)
    record.device = device_memory()
    record.checks = check(config, requests, served)
    record.correct = all(c["value"] <= c["limit"]
                         for c in record.checks.values())
    return record


class _Phases:
    """Seconds and compile events (compiles or persistent-cache loads) in
    each part of set-up, for finding what moves ``setup_s``: each mark
    closes the part since the last one."""

    def __init__(self, t_start: float):
        self.t, self.compiles = t_start, 0
        self.spans: Dict[str, Dict[str, float]] = {}

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.spans[name] = {"s": now - self.t,
                            "compiles": _compiles[0] - self.compiles}
        self.t, self.compiles = now, _compiles[0]


def _closed_window(serve, clients: int, seed: int, close: float):
    """Waves of all ``clients`` at once, each sent when the last is
    answered, for as long as the window is open."""
    requests, served, steps = [], [], []
    wave = 0
    while time.perf_counter() < close:
        sent = time.perf_counter()
        step, answers = serve(clients, sub_seed(seed, "wave", wave))
        requests.extend(Request(due=sent, sent=sent,
                                done=None if a is None else a[2])
                        for a in answers)
        served.extend(answers)
        steps.append(step)
        wave += 1
    return requests, served, steps


def _open_window(serve, due: List[float], seed: int, give_up: float):
    """Each wave is every request due by the time the loop is free; a
    request's lateness is how long after it was due, or after the loop
    was last free, the loop sent it. Requests still unsent at
    ``give_up`` are never answered."""
    requests, served, steps = [], [], []
    i, wave = 0, 0
    free_at = time.perf_counter()
    while i < len(due):
        now = time.perf_counter()
        if now > give_up:
            break
        if due[i] > now:
            with _annotate("client.wait"):
                time.sleep(due[i] - now)
            continue
        j = i
        while j < len(due) and due[j] <= now:
            j += 1
        sent = time.perf_counter()
        step, answers = serve(j - i, sub_seed(seed, "wave", wave))
        requests.extend(Request(due=due[i + k], sent=sent,
                                done=None if a is None else a[2],
                                late=sent - max(due[i + k], free_at))
                        for k, a in enumerate(answers))
        served.extend(answers)
        steps.append(step)
        free_at = time.perf_counter()
        i, wave = j, wave + 1
    for t in due[i:]:
        requests.append(Request(due=t, sent=t))
        served.append(None)
    return requests, served, steps


def device_memory() -> Dict[str, object]:
    """The peak of device memory in use on the fullest chip, where the
    backend reports it."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return {"memory_peak_bytes": max(peaks) if peaks else None}
