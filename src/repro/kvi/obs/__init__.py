"""Unified telemetry for the KVI stack: tracing, metrics, scrubbing.

One :class:`Obs` bundle rides through every execution layer —
``CycleSimBackend``, ``PallasBackend``, ``HartScheduler``,
``ServeEngine`` and the DSE ``sweep()`` all take an optional ``obs=``
parameter (default off, zero overhead). When enabled it collects:

  * a :class:`~repro.kvi.obs.trace.Tracer` — span/instant/counter/flow
    events on dual clocks (virtual cycles + wall seconds), exported as
    Chrome trace-event JSON for Perfetto / ``chrome://tracing``;
  * a :class:`~repro.kvi.obs.metrics.MetricsRegistry` — counters,
    gauges and exact-bucket histograms behind one ``snapshot()``.

Wall time on the served path is not in the bundle: ``PallasBackend``
and ``ServeEngine`` mark it with :func:`~repro.kvi.obs.host.host_span`,
which writes ``kvi.*`` spans into the JAX profiler's trace, on the
device trace's clock, whenever a profiler session is running.

``python -m repro.kvi.obs view TRACE`` summarizes a saved trace (text
timeline + top-k stall attribution); ``... validate TRACE`` checks it
against the kvi-trace-v1 schema. The volatile-key scrubber every
canonical-report producer shares lives in :mod:`repro.kvi.obs.scrub`.
"""
from __future__ import annotations

from dataclasses import dataclass, field

from repro.kvi.obs.host import host_span  # noqa: F401
from repro.kvi.obs.metrics import (NULL_METRICS, Counter, Gauge,  # noqa: F401
                                   Histogram, MetricsRegistry,
                                   NullMetrics, validate_metrics)
from repro.kvi.obs.schema import TRACE_SCHEMA, validate_trace  # noqa: F401
from repro.kvi.obs.scrub import (ALL_VOLATILE, DSE_VOLATILE,  # noqa: F401
                                 SERVE_VOLATILE, TRACE_VOLATILE, scrub)
from repro.kvi.obs.trace import (CLOCK_CYCLES, CLOCK_WALL,  # noqa: F401
                                 NULL_TRACER, NullTracer, Tracer,
                                 canonical_trace, load_trace)


@dataclass
class Obs:
    """The observability bundle instrumented layers thread through.

    Construct with :meth:`on` for a live collector, or pass ``None``
    (the default everywhere) for a true no-op — instrumented code
    guards on ``obs is not None and obs.enabled`` so the disabled path
    costs nothing."""

    tracer: Tracer = field(default_factory=lambda: NULL_TRACER)
    metrics: MetricsRegistry = field(default_factory=lambda: NULL_METRICS)

    @property
    def enabled(self) -> bool:
        return self.tracer.enabled or self.metrics.enabled

    @classmethod
    def on(cls) -> "Obs":
        """A live bundle: fresh tracer + fresh metrics registry."""
        return cls(tracer=Tracer(), metrics=MetricsRegistry())

    def save(self, trace_path=None, metrics_path=None) -> None:
        """Write whatever was collected (either path may be None)."""
        if trace_path:
            self.tracer.save(trace_path)
        if metrics_path:
            self.metrics.save(metrics_path)


#: the canonical disabled bundle (shared; allocates nothing per use)
NULL_OBS = Obs()
