"""2D convolution kernel — the paper's line-buffer conv, re-thought for VMEM.

Klessydra keeps filter rows of the image in SPM and accumulates
ksvmulsc/kaddv taps per output row. On TPU the analogue: the padded image
is VMEM-resident, the grid walks output ROW BLOCKS, and each grid step
accumulates the F*F taps as shifted VPU multiply-adds over a (rows x W)
tile — taps are static Python loops (fully unrolled vector code, no
gather). The filter tile rides in VMEM like an SPM-resident constant.

This variant keeps the whole padded image in VMEM (fine up to ~2k x 2k
f32); a production giant-image variant would stage row slabs via ANY-space
DMA — the paper's images are 4x4..32x32, far below the threshold.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import interpret_mode


def _conv_kernel(img_ref, filt_ref, o_ref, *, F: int, bt: int, W: int,
                 shift: int):
    i = pl.program_id(0)
    acc = jnp.zeros((bt, W), jnp.int32 if img_ref.dtype == jnp.int32
                    else jnp.float32)
    row0 = i * bt
    for fr in range(F):
        # one (bt x W+F-1) slab per filter row, staged once
        slab = img_ref[pl.ds(row0 + fr, bt), :]
        for fc in range(F):
            w = filt_ref[fr, fc]
            acc += slab[:, fc:fc + W].astype(acc.dtype) * w.astype(acc.dtype)
    if shift and jnp.issubdtype(acc.dtype, jnp.integer):
        acc = acc >> shift
    o_ref[...] = acc.astype(o_ref.dtype)


def spm_conv2d(img: jax.Array, filt: jax.Array, *, shift: int = 0,
               block_rows: int = 8) -> jax.Array:
    """img: [H, W] (unpadded); filt: [F, F]. Zero padding, same-size output,
    optional fixed-point post-scale (int32 inputs)."""
    H, W = img.shape
    F = filt.shape[0]
    pad = F // 2
    padded = jnp.pad(img, ((pad, F - 1 - pad), (pad, F - 1 - pad)))
    bt = min(block_rows, H)
    while H % bt:
        bt -= 1
    return pl.pallas_call(
        functools.partial(_conv_kernel, F=F, bt=bt, W=W, shift=shift),
        grid=(H // bt,),
        in_specs=[
            pl.BlockSpec(padded.shape, lambda i: (0, 0)),   # SPM-resident img
            pl.BlockSpec((F, F), lambda i: (0, 0)),         # filter constants
        ],
        out_specs=pl.BlockSpec((bt, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, W), img.dtype),
        interpret=interpret_mode(),
    )(padded, filt)
