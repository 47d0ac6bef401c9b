"""Device time of the reduction kernel's ops in the traced window over
the device's busy time in it, in percent: how much of the chip's work
the walk's reductions (``kdotp``, ``kdotpps``, ``kvred``) are. It reads
0 where the trace holds no reduction kernel: then none of the device's
time went to one.

The trace summary names its ops by opcode and result type only
(``bench.tracing.op_kind``), so the walk's ``kvi.reduce`` scope is not
in it. The reduction kernel is recognised by what ``reduce_rows`` fixes:
it is the only ``tpu_custom_call`` whose result is one int32 ``(N, 128)``
flush tile; a fused region's custom call returns a tuple. Read on one
chip, where ops run one after another, so their times add up to at most
the busy time."""
import re

#: ``op_kind`` of the reduction kernel: an int32 (N, 128) flush tile
REDUCE_KIND = re.compile(r"^custom-call:tpu_custom_call s32\[\d+,128\]$")


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    seconds = sum(s for kind, s in run.trace.top_ops
                  if REDUCE_KIND.match(kind))
    return 100.0 * seconds / run.trace.busy_s
