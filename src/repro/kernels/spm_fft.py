"""Batch radix-2 FFT kernel (the paper's FFT-256, TPU-native).

Klessydra runs one FFT per hart (TLP) with vector butterflies in the SPM.
On TPU the batch dimension IS the lane dimension: the grid walks batch
tiles, and each kernel invocation runs ALL log2(n) stages over a
(batch_tile x n) VMEM-resident block — the data never leaves VMEM between
stages (the SPM-residency insight again; an XLA-op FFT would round-trip
HBM per stage). Contiguous-half DIF butterflies + final bit-reversal via a
static gather, separate re/im planes (no complex dtype on TPU).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import interpret_mode


def _bitrev(n: int) -> np.ndarray:
    bits = int(np.log2(n))
    return np.array([int(f"{i:0{bits}b}"[::-1], 2) for i in range(n)],
                    np.int32)


def _fft_kernel(re_ref, im_ref, perm_ref, ore_ref, oim_ref, *, n: int):
    re = re_ref[...].astype(jnp.float32)        # [bb, n]
    im = im_ref[...].astype(jnp.float32)
    bb = re.shape[0]
    m = n
    while m >= 2:
        h = m // 2
        k = jnp.arange(h, dtype=jnp.float32)
        ang = -2.0 * np.pi * k / m
        wre, wim = jnp.cos(ang), jnp.sin(ang)
        r3 = re.reshape(bb, n // m, m)
        i3 = im.reshape(bb, n // m, m)
        a_re, b_re = r3[:, :, :h], r3[:, :, h:]
        a_im, b_im = i3[:, :, :h], i3[:, :, h:]
        top_re, top_im = a_re + b_re, a_im + b_im
        d_re, d_im = a_re - b_re, a_im - b_im
        bot_re = d_re * wre - d_im * wim
        bot_im = d_re * wim + d_im * wre
        re = jnp.concatenate([top_re, bot_re], axis=2).reshape(bb, n)
        im = jnp.concatenate([top_im, bot_im], axis=2).reshape(bb, n)
        m = h
    perm = perm_ref[...]
    ore_ref[...] = jnp.take(re, perm, axis=1).astype(ore_ref.dtype)
    oim_ref[...] = jnp.take(im, perm, axis=1).astype(oim_ref.dtype)


def spm_fft(re: jax.Array, im: jax.Array, *, batch_block: int = 8):
    """re, im: [B, n] (n a power of two). Returns (re, im) of the DFT."""
    B, n = re.shape
    assert n & (n - 1) == 0, "n must be a power of two"
    bb = min(batch_block, B)
    while B % bb:
        bb -= 1
    fn = pl.pallas_call(
        functools.partial(_fft_kernel, n=n),
        grid=(B // bb,),
        in_specs=[pl.BlockSpec((bb, n), lambda i: (i, 0)),
                  pl.BlockSpec((bb, n), lambda i: (i, 0)),
                  pl.BlockSpec((n,), lambda i: (0,))],
        out_specs=[pl.BlockSpec((bb, n), lambda i: (i, 0)),
                   pl.BlockSpec((bb, n), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, n), jnp.float32),
                   jax.ShapeDtypeStruct((B, n), jnp.float32)],
        interpret=interpret_mode(),
    )
    return fn(re.astype(jnp.float32), im.astype(jnp.float32),
              jnp.asarray(_bitrev(n)))
