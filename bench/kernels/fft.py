"""Kernel ``fft``: the paper's radix-2 decimation-in-frequency FFT of
``points`` complex points, twiddles in Q``q``, no scaling between
stages.

Work: ``n/2 * log2(n)`` butterflies of ten operations (a complex add, a
complex subtract, a complex multiply of four products and two sums). A
request reads ``n`` complex inputs and ``n/2`` complex twiddles and
writes ``n`` complex outputs.
"""
from __future__ import annotations

from typing import Dict, Mapping, Tuple

import numpy as np

from bench.reference import wrap32


def program(config: Mapping, elem_bytes: int):
    """The request template: zero inputs (the twiddles are the
    program's own)."""
    from repro.kvi.programs import fft_program
    z = np.zeros(config["points"], np.int32)
    return fft_program(z, z, elem_bytes=elem_bytes)


def twiddles(m: int, q: int) -> tuple:
    """``exp(-2 pi i k / m)`` for ``k < m/2`` in Q``q``, truncated toward
    zero."""
    k = np.arange(m // 2)
    ang = 2.0 * np.pi * k / m
    return (np.trunc(np.cos(ang) * (1 << q)).astype(np.int64),
            np.trunc(-np.sin(ang) * (1 << q)).astype(np.int64))


def fft_dif(re: np.ndarray, im: np.ndarray, q: int = 15) -> tuple:
    """``(R, n)`` real and imaginary parts -> the radix-2
    decimation-in-frequency FFT in natural order, twiddles in Q``q``,
    each product shifted back by ``q``; no scaling between stages."""
    re = re.astype(np.int64).copy()
    im = im.astype(np.int64).copy()
    R, n = re.shape
    m = n
    while m >= 2:
        h = m // 2
        wre, wim = twiddles(m, q)
        a_re = re.reshape(R, n // m, m)
        a_im = im.reshape(R, n // m, m)
        lo_re, hi_re = a_re[..., :h].copy(), a_re[..., h:].copy()
        lo_im, hi_im = a_im[..., :h].copy(), a_im[..., h:].copy()
        d_re = wrap32(lo_re - hi_re)
        d_im = wrap32(lo_im - hi_im)
        a_re[..., :h] = wrap32(lo_re + hi_re)
        a_im[..., :h] = wrap32(lo_im + hi_im)
        a_re[..., h:] = wrap32((wrap32(d_re * wre) >> q)
                               - (wrap32(d_im * wim) >> q))
        a_im[..., h:] = wrap32((wrap32(d_re * wim) >> q)
                               + (wrap32(d_im * wre) >> q))
        m = h
    bits = n.bit_length() - 1
    rev = np.array([int(f"{i:0{bits}b}"[::-1], 2) for i in range(n)])
    out_re = np.empty_like(re)
    out_im = np.empty_like(im)
    out_re[:, rev] = re
    out_im[:, rev] = im
    return out_re.astype(np.int32), out_im.astype(np.int32)


def expected(config: Mapping, inputs: Mapping[str, np.ndarray]
             ) -> Dict[str, np.ndarray]:
    """``out_re``/``out_im`` of every request, from ``x_re``/``x_im``."""
    re, im = fft_dif(inputs["x_re"], inputs["x_im"], config["q"])
    return {"out_re": re, "out_im": im}


def fft_work(n: int, elem_bytes: int) -> Tuple[int, int]:
    stages = n.bit_length() - 1
    if 1 << stages != n:
        raise ValueError(f"FFT size {n} is not a power of two")
    butterflies = n // 2 * stages
    nbytes = (2 * n + n + 2 * n) * elem_bytes
    return 10 * butterflies, nbytes


def work(config: Mapping) -> Tuple[int, int]:
    return fft_work(config["points"], config["elem_bytes"])
