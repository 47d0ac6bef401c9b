"""Least time at the chip's peaks for the algorithmic work of the
requests answered in the traced window (``bench/work.py``), over the
device's busy time in it, in percent. Each request's least time is the
larger of its bytes over HBM bandwidth and its operations over the int8
peak."""
from bench.work import least_time_s


def read(run):
    if run.trace is None or run.peak is None or run.trace.busy_s <= 0:
        return None
    least = sum(least_time_s(r.ops, r.nbytes, run.peak)["seconds"]
                for r in run.requests if r.done is not None)
    if least <= 0:
        return None
    return 100.0 * least / run.trace.busy_s
