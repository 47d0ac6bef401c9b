"""Reduction kernels: kdotp / kdotpps / kvred (paper Table 1).

One kernel reduces every row of an ``(N, n)`` batch: the grid streams
SPM-line-sized ``(N, bl)`` tiles through VMEM, a vector-shaped ``(N, bl)``
accumulator scratch carries the element-wise partial sums across grid
steps (the MFU's adder tree), and the last step folds the lanes and
flushes one ``(N, 128)`` tile whose column 0 is the result. kdotpps
applies the post-scaling arithmetic shift at flush, exactly like the
hardware writes the scaled dot product to the register file. Sub-word
integer inputs widen to int32 in the body; the sum wraps like the int32
register it lands in.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_mode, pick_block

_LANES = 128


def _reduce_kernel(*refs, n_blocks: int, mul: bool, shift: int, acc_dtype):
    if mul:
        a_ref, b_ref, o_ref, acc_ref = refs
    else:
        a_ref, o_ref, acc_ref = refs
        b_ref = None
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(acc_dtype)
    acc_ref[...] += a * b_ref[...].astype(acc_dtype) if mul else a

    @pl.when(i == n_blocks - 1)
    def _flush():
        r = jnp.sum(acc_ref[...], axis=1, keepdims=True)
        if shift:
            r = r >> jnp.asarray(shift, r.dtype) if \
                jnp.issubdtype(acc_dtype, jnp.integer) else \
                r / jnp.asarray(2.0 ** shift, r.dtype)
        o_ref[...] = jnp.broadcast_to(r, o_ref.shape)


def reduce_rows(a: jax.Array, b: Optional[jax.Array] = None, *,
                shift: int = 0, block: int = 2048) -> jax.Array:
    """Row reductions of an ``(N, n)`` batch in one launch: ``sum(a * b)``
    (kdotp, kdotpps with ``shift``) or ``sum(a)`` (kvred) per row.
    Returns ``(N,)`` int32 for integer inputs, float32 otherwise."""
    N, n = a.shape
    bl = pick_block(n, block)
    acc_dtype = jnp.int32 if jnp.issubdtype(a.dtype, jnp.integer) \
        else jnp.float32
    mul = b is not None
    args = [a] + ([b] if mul else [])
    out = pl.pallas_call(
        functools.partial(_reduce_kernel, n_blocks=n // bl, mul=mul,
                          shift=shift, acc_dtype=acc_dtype),
        grid=(n // bl,),
        in_specs=[pl.BlockSpec((N, bl), lambda i: (0, i)) for _ in args],
        out_specs=pl.BlockSpec((N, _LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, _LANES), acc_dtype),
        scratch_shapes=[pltpu.VMEM((N, bl), acc_dtype)],
        interpret=interpret_mode(),
    )(*args)
    return out[:, 0]


def kdotp(a: jax.Array, b: jax.Array, *, block: int = 2048):
    return reduce_rows(jnp.ravel(a)[None], jnp.ravel(b)[None],
                       block=block)[0]


def kdotpps(a: jax.Array, b: jax.Array, shift: int, *, block: int = 2048):
    return reduce_rows(jnp.ravel(a)[None], jnp.ravel(b)[None], shift=shift,
                       block=block)[0]


def kvred(a: jax.Array, *, block: int = 2048):
    return reduce_rows(jnp.ravel(a)[None], block=block)[0]
