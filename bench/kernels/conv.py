"""Kernel ``conv``: the paper's 2-D convolution, an ``F x F`` filter
over an ``S x S`` image (``image_size``, ``filter_size``), then an
arithmetic shift right by ``shift``.

Work: ``S*S*F*F`` multiply-accumulates, two operations each. The request
carries the zero-padded ``(S+F-1)^2`` image and returns ``S*S`` outputs;
the filter is ``F*F`` elements.
"""
from __future__ import annotations

from typing import Dict, Mapping, Tuple

import numpy as np

from bench.reference import wrap32


def conv_filter(config: Mapping) -> np.ndarray:
    """The configuration's filter: fixed by its ``weight_seed``, the same
    in every run, so the compiled kernels (which hold the filter taps as
    immediates) are the same in every run."""
    F, lim = config["filter_size"], config["filter_limit"]
    rng = np.random.default_rng(config["weight_seed"])
    return rng.integers(-lim, lim, (F, F)).astype(np.int32)


def program(config: Mapping, elem_bytes: int):
    """The request template: zero image, the configuration's filter."""
    from repro.kvi.programs import conv2d_program
    S = config["image_size"]
    return conv2d_program(np.zeros((S, S), np.int32), conv_filter(config),
                          shift=config["shift"], elem_bytes=elem_bytes)


def conv2d(padded: np.ndarray, filt: np.ndarray, shift: int) -> np.ndarray:
    """``(R, S+F-1, S+F-1)`` padded images -> ``(R, S, S)``: the valid
    correlation with ``filt``, then an arithmetic shift right."""
    padded = padded.astype(np.int64)
    F = filt.shape[0]
    S = padded.shape[1] - F + 1
    acc = np.zeros((padded.shape[0], S, S), np.int64)
    for fr in range(F):
        for fc in range(F):
            prod = wrap32(padded[:, fr:fr + S, fc:fc + S] * int(filt[fr, fc]))
            acc = wrap32(acc + prod)
    return (acc >> shift).astype(np.int32)


def expected(config: Mapping, inputs: Mapping[str, np.ndarray]
             ) -> Dict[str, np.ndarray]:
    """``row<i>`` of every request, from its padded image ``img``."""
    S, F = config["image_size"], config["filter_size"]
    Sp = S + F - 1
    imgs = inputs["img"].reshape(-1, Sp, Sp)
    out = conv2d(imgs, conv_filter(config), config["shift"])
    return {f"row{i}": out[:, i, :] for i in range(S)}


def conv_work(S: int, F: int, elem_bytes: int) -> Tuple[int, int]:
    macs = S * S * F * F
    Sp = S + F - 1
    nbytes = (Sp * Sp + S * S + F * F) * elem_bytes
    return 2 * macs, nbytes


def work(config: Mapping) -> Tuple[int, int]:
    return conv_work(config["image_size"], config["filter_size"],
                     config["elem_bytes"])
