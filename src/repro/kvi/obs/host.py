"""Host spans on the JAX profiler's clock.

The served path (``ServeEngine`` over ``PallasBackend``) marks its layer
boundaries with :func:`host_span`, which writes a
``jax.profiler.TraceAnnotation`` into the profiler's own trace. The
spans therefore share the device trace's clock: a gap in which the
device sat idle can be named by what the host was doing in it. Any
``jax.profiler`` session of the process records them; outside one a
span costs only the profiler's enabled check, so there is nothing to
switch on or off.

Every span name is a constant here, so this module is the one list of
names a trace reader matches against (all start with ``kvi.``).
The walk is one compiled device program, so no span marks a single
instruction: a span inside the traced body would fire only while it is
traced.
"""
from __future__ import annotations

import contextlib
import sys

#: prefix shared by every program span
PREFIX = "kvi."

# PallasBackend.run_workload
RUN_WORKLOAD = "kvi.backend.run_workload"   # the whole call
PREPARE = "kvi.backend.prepare"             # optimize + structural grouping
# PallasBackend._run_batch: one batched walk, one compiled call
WALK = "kvi.walk"
WALK_BUILD = "kvi.walk.build"               # trace + compile (first batch)
WALK_STAGE = "kvi.walk.stage"               # stack the loaded buffers
WALK_CALL = "kvi.walk.call"                 # dispatch the compiled walk
WALK_SYNC = "kvi.walk.sync"                 # its one device->host fetch
WALK_OUTPUTS = "kvi.walk.outputs"           # per-request output copies
# ServeEngine
ENGINE_RUN = "kvi.engine.run"               # the whole run()
ENGINE_ADMIT = "kvi.engine.admit"           # one step's admission + grouping
ENGINE_INSTANTIATE = "kvi.engine.instantiate"  # one bucket's programs
ENGINE_REPORT = "kvi.engine.report"         # report() + telemetry

SPANS = (RUN_WORKLOAD, PREPARE, WALK, WALK_BUILD, WALK_STAGE, WALK_CALL,
         WALK_SYNC, WALK_OUTPUTS, ENGINE_RUN, ENGINE_ADMIT,
         ENGINE_INSTANTIATE, ENGINE_REPORT)

# Device-side names: ``jax.named_scope``s inside the traced walk. They
# are no host spans; they name the compiled walk's ops (the HLO name of
# a region's or reduction's custom call starts with its scope).
REGION_SCOPE = "kvi.region"                 # one fused element-wise region
REDUCE_SCOPE = "kvi.reduce"                 # one batched reduction kernel

_NO_SPAN = contextlib.nullcontext()


def host_span(name: str, **args):
    """A context manager marking ``name`` on the profiler's host trace,
    with ``args`` as the event's metadata. Where nothing has imported
    JAX no profiler session can be running, so the schedule-only engine
    gets a no-op and never imports JAX for a span."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NO_SPAN
    return jax.profiler.TraceAnnotation(name, **args)
