"""Mamba-2 SSD chunk-scan kernel.

The SSD recurrence state [P, N] is exactly an SPM-resident accumulator: the
grid walks (batch x head x chunk) with the chunk axis innermost, the state
rides in VMEM scratch between chunks (never touching HBM), and each step
does the intra-chunk quadratic work as MXU matmuls on VMEM tiles.

Inputs are pre-projected (x, da=dt*A, dt, B, C) — the surrounding jitted op
(repro.kernels.ops.ssd_scan_op) handles the head-group broadcast.
Oracle: repro.models.ssm.ssd_chunked / ssd_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_mode


def _ssd_kernel(x_ref, da_ref, dt_ref, b_ref, c_ref, y_ref, state_ref,
                h_ref, *, cs: int, n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, :, 0].astype(jnp.float32)            # [cs, P]
    da = da_ref[0, :, 0].astype(jnp.float32)          # [cs]
    dt = dt_ref[0, :, 0].astype(jnp.float32)          # [cs]
    B = b_ref[0, :, 0].astype(jnp.float32)            # [cs, N]
    C = c_ref[0, :, 0].astype(jnp.float32)            # [cs, N]

    cum = jnp.cumsum(da)                              # [cs]
    # intra-chunk: seg[i,j] = exp(cum_i - cum_j) for i>=j
    diff = cum[:, None] - cum[None, :]
    tril = jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 1)
    seg = jnp.where(tril, jnp.exp(jnp.where(tril, diff, 0.0)), 0.0)
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [cs, cs]
    xdt = x * dt[:, None]
    y = jax.lax.dot_general(cb * seg, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [cs, P]

    # inter-chunk: contribution of the carried state
    decay_in = jnp.exp(cum)                           # [cs]
    h = h_ref[...]                                    # [N, P]
    y += decay_in[:, None] * jax.lax.dot_general(
        C, h, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    # state update: h' = exp(sum da) * h + sum_j exp(cum_last - cum_j) Bj xdtj
    decay_out = jnp.exp(cum[-1] - cum)                # [cs]
    upd = jax.lax.dot_general(B * decay_out[:, None], xdt,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [N, P]
    h_ref[...] = jnp.exp(cum[-1]) * h + upd

    y_ref[0, :, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _flush():
        state_ref[0, 0] = h_ref[...].astype(state_ref.dtype)


def ssd_scan(x: jax.Array, da: jax.Array, dt: jax.Array, B: jax.Array,
             C: jax.Array, *, chunk: int = 256):
    """x: [Bz, S, H, P]; da, dt: [Bz, S, H]; B, C: [Bz, S, H, N] (already
    head-broadcast). Returns (y [Bz,S,H,P], state [Bz,H,N,P])."""
    Bz, S, H, P = x.shape
    N = B.shape[-1]
    cs = min(chunk, S)
    assert S % cs == 0
    n_chunks = S // cs

    grid = (Bz, H, n_chunks)
    y, state = pl.pallas_call(
        functools.partial(_ssd_kernel, cs=cs, n_chunks=n_chunks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, cs, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, cs, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1, cs, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1, cs, 1, N), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, cs, 1, N), lambda b, h, c: (b, c, h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, cs, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bz, S, H, P), x.dtype),
            jax.ShapeDtypeStruct((Bz, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret_mode(),
    )(x, da, dt, B, C)
    return y, state
