"""Kernel ``matmul``: the paper's matrix multiplication ``C = A B`` of an
``n x m`` A by an ``m x p`` B in its streamed form: each element of C is
one ``kdotpps``, the dot product of a row of A and a column of B, each
product and each partial sum wrapped to 32 bits, then shifted
arithmetically right by ``shift``.

A is the layer's fixed weights, drawn from the configuration's
``weight_seed`` and shared by every request. A request carries B as
``p`` column buffers ``bcol<j>`` (``B[:, j]``) and returns C as ``n``
row buffers ``row<i>``.

Work: ``n*m*p`` multiply-accumulates, two operations each. A request
moves A, B and C, ``elem_bytes`` an element.
"""
from __future__ import annotations

from typing import Dict, Mapping, Tuple

import numpy as np

from bench.reference import wrap32


def weights(config: Mapping) -> np.ndarray:
    """The configuration's A: fixed by its ``weight_seed``, the same in
    every run, drawn from ``[-weight_limit, weight_limit)``."""
    lim = config["weight_limit"]
    rng = np.random.default_rng(config["weight_seed"])
    return rng.integers(-lim, lim, (config["n"], config["m"])).astype(
        np.int32)


def program(config: Mapping, elem_bytes: int):
    """The request template, streamed: the configuration's A, zero B.
    Refused where the program does not hold B as the configuration's
    column buffers: a request would then carry no data of its own."""
    from repro.kvi.programs import matmul_program
    B = np.zeros((config["m"], config["p"]), np.int32)
    prog = matmul_program(weights(config), B, shift=config["shift"],
                          resident=False, elem_bytes=elem_bytes)
    missing = set(config["data_mems"]) - {m.name for m in prog.mems}
    if missing:
        raise ValueError(f"the streamed matmul program has no buffers "
                         f"{sorted(missing)[:4]}...: it does not hold B "
                         f"as one buffer per column")
    return prog


def matmul(A: np.ndarray, B: np.ndarray, shift: int) -> np.ndarray:
    """``(n, m)`` A by ``(R, m, p)`` Bs -> ``(R, n, p)``: each product
    and each partial sum wrapped to 32 bits, then an arithmetic shift
    right."""
    A = A.astype(np.int64)
    B = B.astype(np.int64)
    acc = np.zeros((B.shape[0], A.shape[0], B.shape[2]), np.int64)
    for k in range(A.shape[1]):
        acc = wrap32(acc + wrap32(A[None, :, k, None] * B[:, None, k, :]))
    return (acc >> shift).astype(np.int32)


def expected(config: Mapping, inputs: Mapping[str, np.ndarray]
             ) -> Dict[str, np.ndarray]:
    """``row<i>`` of every request, from its columns ``bcol<j>``."""
    B = np.stack([inputs[f"bcol{j}"] for j in range(config["p"])], axis=2)
    out = matmul(weights(config), B, config["shift"])
    return {f"row{i}": out[:, i, :] for i in range(config["n"])}


def matmul_work(n: int, m: int, p: int, elem_bytes: int) -> Tuple[int, int]:
    nbytes = (n * m + m * p + n * p) * elem_bytes
    return 2 * n * m * p, nbytes


def work(config: Mapping) -> Tuple[int, int]:
    return matmul_work(config["n"], config["m"], config["p"],
                       config["elem_bytes"])
