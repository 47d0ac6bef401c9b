"""Algorithmic work of one request, counted from the configuration's
shapes and never from what the program launches: operations and the
bytes a request must move at least (its inputs, weights and outputs).
Each kernel counts its own in ``bench/kernels/<kernel>.py``'s ``work``;
this module holds the least time the chip could take for such work.
"""
from __future__ import annotations

from typing import Dict

from bench.peaks import Peak


def least_time_s(ops: float, nbytes: float, peak: Peak) -> Dict[str, object]:
    """The least time the chip could take for ``ops`` integer operations
    and ``nbytes`` bytes: the larger of the two bounds, and which it is.
    Integer work is held to the int8 peak, the highest the chip has, so
    the bound is never above what any precision could reach."""
    t_ops = ops / peak.int8_ops
    t_bytes = nbytes / peak.hbm_bytes_per_s
    if t_bytes >= t_ops:
        return {"seconds": t_bytes, "bound": "hbm_bytes"}
    return {"seconds": t_ops, "bound": "int8_ops"}
