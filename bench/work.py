"""Algorithmic work of one request, counted from the configuration's
shapes and never from what the program launches: operations and the
bytes a request must move at least (its inputs, weights and outputs).

conv: an ``F x F`` filter over an ``S x S`` image is ``S*S*F*F``
multiply-accumulates, two operations each. The request carries the
zero-padded ``(S+F-1)^2`` image, and returns ``S*S`` outputs; the filter
is ``F*F`` elements.

fft: a radix-2 FFT of ``n`` points is ``n/2 * log2(n)`` butterflies of
ten operations (a complex add, a complex subtract, a complex multiply of
four products and two sums). It reads ``n`` complex inputs and ``n/2``
complex twiddles and writes ``n`` complex outputs.
"""
from __future__ import annotations

from typing import Dict, Mapping, Tuple

from bench.peaks import Peak


def conv_work(S: int, F: int, elem_bytes: int) -> Tuple[int, int]:
    macs = S * S * F * F
    Sp = S + F - 1
    nbytes = (Sp * Sp + S * S + F * F) * elem_bytes
    return 2 * macs, nbytes


def fft_work(n: int, elem_bytes: int) -> Tuple[int, int]:
    stages = n.bit_length() - 1
    if 1 << stages != n:
        raise ValueError(f"FFT size {n} is not a power of two")
    butterflies = n // 2 * stages
    nbytes = (2 * n + n + 2 * n) * elem_bytes
    return 10 * butterflies, nbytes


def request_work(config: Mapping) -> Tuple[int, int]:
    """``(operations, bytes)`` of one request of ``config``."""
    kernel = config["kernel"]
    if kernel == "conv":
        return conv_work(config["image_size"], config["filter_size"],
                         config["elem_bytes"])
    if kernel == "fft":
        return fft_work(config["points"], config["elem_bytes"])
    raise ValueError(f"no work count for kernel {kernel!r}")


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
