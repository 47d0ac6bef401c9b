"""Heterogeneous-MIMD composite kernel — the paper's headline scheme on TPU.

Klessydra het-MIMD: one shared MFU, per-hart SPM interfaces, three harts
running DIFFERENT kernels (conv / FFT / MatMul) concurrently. TPU analogue:
ONE pallas_call whose grid axis is the "hart" id; each grid step executes a
different tile program (switched on program_id) against its own dedicated
VMEM blocks — one compute engine (VPU/MXU), disjoint scratchpads,
interleaved heterogeneous execution. The paper's composite workload
(convoluting an image while FFT-ing audio while MatMul-ing for crypto)
runs as a single fused launch.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import interpret_mode
from repro.kernels.spm_fft import _bitrev


def _composite_kernel(img_ref, filt_ref, fre_ref, fim_ref, a_ref, b_ref,
                      perm_ref, conv_ref, ore_ref, oim_ref, mm_ref, *,
                      F: int, n: int):
    hart = pl.program_id(0)

    def conv_branch():
        H, W = conv_ref.shape
        acc = jnp.zeros((H, W), jnp.float32)
        for fr in range(F):
            for fc in range(F):
                acc += img_ref[fr:fr + H, fc:fc + W].astype(jnp.float32) * \
                    filt_ref[fr, fc].astype(jnp.float32)
        conv_ref[...] = acc.astype(conv_ref.dtype)

    def fft_branch():
        re = fre_ref[...].astype(jnp.float32)
        im = fim_ref[...].astype(jnp.float32)
        bb = re.shape[0]
        m = n
        while m >= 2:
            h = m // 2
            k = jnp.arange(h, dtype=jnp.float32)
            ang = -2.0 * np.pi * k / m
            wre, wim = jnp.cos(ang), jnp.sin(ang)
            r3 = re.reshape(bb, n // m, m)
            i3 = im.reshape(bb, n // m, m)
            a, br = r3[:, :, :h], r3[:, :, h:]
            ai, bi = i3[:, :, :h], i3[:, :, h:]
            re = jnp.concatenate([a + br, (a - br) * wre - (ai - bi) * wim],
                                 axis=2).reshape(bb, n)
            im = jnp.concatenate([ai + bi, (a - br) * wim + (ai - bi) * wre],
                                 axis=2).reshape(bb, n)
            m = h
        perm = perm_ref[...]
        ore_ref[...] = jnp.take(re, perm, axis=1)
        oim_ref[...] = jnp.take(im, perm, axis=1)

    def mm_branch():
        mm_ref[...] = jax.lax.dot_general(
            a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(mm_ref.dtype)

    # the "hart id" selects the tile program; all branches share the same
    # compute engine but write disjoint VMEM outputs (dedicated SPMIs)
    jax.lax.switch(hart, [conv_branch, fft_branch, mm_branch])


def het_mimd_composite(img, filt, fft_re, fft_im, A, B):
    """Run conv2d(img, filt) + FFT(fft_re/im) + A@B in ONE kernel launch.
    img: [H+F-1, W+F-1] (pre-padded), filt: [F,F], fft_*: [nb, n],
    A: [m, k], B: [k, p]. Returns (conv [H,W], fft_re, fft_im, A@B)."""
    F = filt.shape[0]
    H, W = img.shape[0] - F + 1, img.shape[1] - F + 1
    nb, n = fft_re.shape
    m, kk = A.shape
    _, p = B.shape

    full = lambda shape: pl.BlockSpec(shape, lambda h: tuple(0 for _ in shape))
    outs = pl.pallas_call(
        functools.partial(_composite_kernel, F=F, n=n),
        grid=(3,),
        in_specs=[full(img.shape), full(filt.shape), full(fft_re.shape),
                  full(fft_im.shape), full(A.shape), full(B.shape),
                  full((n,))],
        out_specs=[full((H, W)), full((nb, n)), full((nb, n)), full((m, p))],
        out_shape=[
            jax.ShapeDtypeStruct((H, W), jnp.float32),
            jax.ShapeDtypeStruct((nb, n), jnp.float32),
            jax.ShapeDtypeStruct((nb, n), jnp.float32),
            jax.ShapeDtypeStruct((m, p), jnp.float32),
        ],
        interpret=interpret_mode(),
    )(img, filt, fft_re, fft_im, A, B, jnp.asarray(_bitrev(n)))
    return outs
