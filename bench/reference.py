"""Plain numpy references of the configurations' kernels, written from
the paper's description and independent of the program under test.

Both compute in 32-bit two's-complement fixed point: every sum and
product wraps to 32 bits, and a right shift is arithmetic. They work on
a batch of requests at once (leading axis).
"""
from __future__ import annotations

from typing import Dict, Mapping

import numpy as np


def _wrap32(x: np.ndarray) -> np.ndarray:
    return x.astype(np.int64).astype(np.int32).astype(np.int64)


def conv_filter(config: Mapping) -> np.ndarray:
    """The configuration's filter: fixed by its ``weight_seed``, the same
    in every run, so the compiled kernels (which hold the filter taps as
    immediates) are the same in every run."""
    F, lim = config["filter_size"], config["filter_limit"]
    rng = np.random.default_rng(config["weight_seed"])
    return rng.integers(-lim, lim, (F, F)).astype(np.int32)


def conv2d(padded: np.ndarray, filt: np.ndarray, shift: int) -> np.ndarray:
    """``(R, S+F-1, S+F-1)`` padded images -> ``(R, S, S)``: the valid
    correlation with ``filt``, then an arithmetic shift right."""
    padded = padded.astype(np.int64)
    F = filt.shape[0]
    S = padded.shape[1] - F + 1
    acc = np.zeros((padded.shape[0], S, S), np.int64)
    for fr in range(F):
        for fc in range(F):
            prod = _wrap32(padded[:, fr:fr + S, fc:fc + S] * int(filt[fr, fc]))
            acc = _wrap32(acc + prod)
    return (acc >> shift).astype(np.int32)


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
        d_re = _wrap32(lo_re - hi_re)
        d_im = _wrap32(lo_im - hi_im)
        a_re[..., :h] = _wrap32(lo_re + hi_re)
        a_im[..., :h] = _wrap32(lo_im + hi_im)
        a_re[..., h:] = _wrap32((_wrap32(d_re * wre) >> q)
                                - (_wrap32(d_im * wim) >> q))
        a_im[..., h:] = _wrap32((_wrap32(d_re * wim) >> q)
                                + (_wrap32(d_im * wre) >> q))
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
    """What a batch of requests of ``config`` must return, by output
    name, each ``(R, ...)``: ``row<i>`` for conv, ``out_re``/``out_im``
    for fft."""
    kernel = config["kernel"]
    if kernel == "conv":
        S, F = config["image_size"], config["filter_size"]
        Sp = S + F - 1
        imgs = inputs["img"].reshape(-1, Sp, Sp)
        out = conv2d(imgs, conv_filter(config), config["shift"])
        return {f"row{i}": out[:, i, :] for i in range(S)}
    if kernel == "fft":
        re, im = fft_dif(inputs["x_re"], inputs["x_im"], config["q"])
        return {"out_re": re, "out_im": im}
    raise ValueError(f"no reference for kernel {kernel!r}")
