"""DEPRECATED — the untyped tuple protocol for fused element-wise KVI
programs. Superseded by the typed IR in ``repro.kvi`` (author programs
with :class:`repro.kvi.KviProgramBuilder`, run them on the ``pallas``
backend) and, at this level, by
:func:`repro.kvi.pallas_backend.fused_elementwise_call`.

Kept for one release so existing call sites keep working; ``run_vops``
now just adapts the tuple encoding onto the new executor and warns.
"""
from __future__ import annotations

import warnings
from typing import Optional, Sequence, Tuple

import jax

from repro.kvi.pallas_backend import apply_vop, fused_elementwise_call

# (op, dst_slot, src1_slot, src2_slot_or_None, immediate)
VOp = Tuple[str, int, int, Optional[int], int]

_ELEMWISE = {"kaddv", "ksubv", "kvmul", "ksvaddsc", "ksvmulsc", "ksrlv",
             "ksrav", "krelu", "kvslt", "ksvslt", "kvcp"}

__all__ = ["VOp", "apply_vop", "run_vops"]


def run_vops(program: Sequence[VOp], inputs: Sequence[jax.Array],
             out_slot: Optional[int] = None, n_slots: Optional[int] = None,
             block: int = 1024) -> jax.Array:
    """Execute a KVI element-wise program over equal-shaped input vectors.

    .. deprecated:: use ``repro.kvi`` (typed IR + pallas backend); this
       shim forwards to
       :func:`repro.kvi.pallas_backend.fused_elementwise_call`.
    """
    warnings.warn(
        "repro.kernels.kvi_vops.run_vops is deprecated; build a typed "
        "program with repro.kvi.KviProgramBuilder or call "
        "repro.kvi.pallas_backend.fused_elementwise_call directly",
        DeprecationWarning, stacklevel=2)
    program = tuple(program)
    for op, *_ in program:
        if op not in _ELEMWISE:
            raise ValueError(f"{op} is not an element-wise KVI op")
    if n_slots is None:
        n_slots = max([len(inputs)] + [o[1] + 1 for o in program])
    if out_slot is None:
        out_slot = program[-1][1]
    x0 = inputs[0]
    out, = fused_elementwise_call(program, list(enumerate(inputs)),
                                  [out_slot], n_slots=n_slots, block=block)
    return out.reshape(x0.shape)
