"""SPM-tiled matmul kernel (the paper's MatMul, TPU-native).

The Klessydra MatMul streams B through the SPM because 16 KiB doesn't fit;
on TPU the same discipline becomes: stage (bm x bk) and (bk x bn) tiles in
VMEM via BlockSpecs, accumulate in an f32 VMEM scratch across the K grid
dimension, write the (bm x bn) output tile once (MXU-aligned 128x128x128
default tiles). Sub-word SIMD (paper: 8/16/32-bit elements) becomes the
dtype parameter: int8 inputs accumulate in int32, bf16 in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_mode, pick_block


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int, out_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=acc_ref.dtype)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def spm_matmul(a: jax.Array, b: jax.Array, *, bm: int = 128, bn: int = 128,
               bk: int = 128, out_dtype=None):
    """a: [M, K] @ b: [K, N] -> [M, N]. int8 -> int32 accumulate; floats ->
    f32 accumulate in VMEM scratch."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    if out_dtype is None:
        out_dtype = jnp.int32 if a.dtype == jnp.int8 else a.dtype
    acc_dtype = jnp.int32 if a.dtype == jnp.int8 else jnp.float32
    bm, bn, bk = (pick_block(M, bm), pick_block(N, bn), pick_block(K, bk))
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    n_k = K // bk

    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=n_k, out_dtype=out_dtype),
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        interpret=interpret_mode(),
    )(a, b)
