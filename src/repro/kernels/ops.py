"""Jitted public wrappers for the Pallas kernels — the "intrinsics" layer
(the paper exposes its ISA as GCC intrinsics; we expose ours as jitted jax
ops). Model code calls these; each has a matching oracle in ref.py.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.kernels import kdotp as _kdotp
from repro.kernels.flash_attention import flash_attention
from repro.kernels.spm_conv2d import spm_conv2d
from repro.kernels.spm_fft import spm_fft
from repro.kernels.spm_matmul import spm_matmul
from repro.kvi.pallas_backend import fused_elementwise_call


# ---- KVI element-wise intrinsics (single-op / fused slot programs) ---------

def _ew(program, inputs):
    """One fused pallas_call over the slot program; inputs occupy slots
    0..n-1, the last op's dst slot is the result."""
    out, = fused_elementwise_call(program, list(enumerate(inputs)),
                                  [program[-1][1]])
    return out.reshape(inputs[0].shape)


def kaddv(a, b):
    return _ew([("kaddv", 2, 0, 1, 0)], [a, b])


def ksubv(a, b):
    return _ew([("ksubv", 2, 0, 1, 0)], [a, b])


def kvmul(a, b):
    return _ew([("kvmul", 2, 0, 1, 0)], [a, b])


def krelu(a):
    return _ew([("krelu", 1, 0, None, 0)], [a])


def ksvaddsc(a, imm: int):
    return _ew([("ksvaddsc", 1, 0, None, imm)], [a])


def ksvmulsc(a, imm: int):
    return _ew([("ksvmulsc", 1, 0, None, imm)], [a])


def ksrlv(a, imm: int):
    return _ew([("ksrlv", 1, 0, None, imm)], [a])


def ksrav(a, imm: int):
    return _ew([("ksrav", 1, 0, None, imm)], [a])


def kvslt(a, b):
    return _ew([("kvslt", 2, 0, 1, 0)], [a, b])


def ksvslt(a, imm: int):
    return _ew([("ksvslt", 1, 0, None, imm)], [a])


def kvcp(a):
    return _ew([("kvcp", 1, 0, None, 0)], [a])


# fused example: relu(a*w + b) >> s — one HBM pass, four KVI ops in VMEM
def fused_mac_relu(a, w, b, shift: int):
    prog = [("kvmul", 3, 0, 1, 0),
            ("kaddv", 3, 3, 2, 0),
            ("ksrav", 3, 3, None, shift),
            ("krelu", 3, 3, None, 0)]
    return _ew(prog, [a, w, b])


# ---- reductions -------------------------------------------------------------

kdotp = _kdotp.kdotp
kdotpps = _kdotp.kdotpps
kvred = _kdotp.kvred


# ---- compute kernels --------------------------------------------------------

matmul_op = jax.jit(spm_matmul, static_argnames=("bm", "bn", "bk",
                                                 "out_dtype"))
conv2d_op = jax.jit(spm_conv2d, static_argnames=("shift", "block_rows"))
fft_op = jax.jit(spm_fft, static_argnames=("batch_block",))
attention_op = jax.jit(flash_attention,
                       static_argnames=("causal", "window", "bq", "bk",
                                        "q_offset"))


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan_op(x, dt, A, B, C, *, chunk: int = 256):
    """Model-facing wrapper: x [Bz,S,H,P], dt [Bz,S,H], A [H],
    B/C [Bz,S,G,N] (GQA-style groups) — broadcasts groups to heads,
    precomputes da = dt*A, calls the kernel."""
    from repro.kernels.ssd_scan import ssd_scan
    H = x.shape[2]
    G = B.shape[2]
    rep = H // G
    Bh = jnp.repeat(B, rep, axis=2)
    Ch = jnp.repeat(C, rep, axis=2)
    da = dt * A[None, None, :]
    y, state = ssd_scan(x, da, dt, Bh, Ch, chunk=chunk)
    return y, state
