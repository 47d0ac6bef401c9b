"""Shared kernel utilities.

All kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling) and are
validated on CPU with the Pallas interpreter against the pure-jnp oracles
in ref.py. The SPM discipline from the paper maps 1:1: BlockSpecs stage
HBM->VMEM lines (kmemld), kernel bodies are fused KVI programs operating on
VMEM-resident tiles (MFU), outputs stream back (kmemstr).
"""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """How every Pallas kernel runs, decided at call time from JAX's
    default platform: compiled with Mosaic on a TPU, interpreted on the
    CPU (where the tests run). Any other platform raises, so nothing
    silently measures the interpreter in place of the chip."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels run compiled on a TPU or "
                       f"interpreted on the CPU; JAX's default platform "
                       f"is {platform!r}")


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def pick_block(dim: int, preferred: int, align: int = 128) -> int:
    """Largest hardware-aligned block <= preferred that divides dim, or dim
    itself when it is small/unaligned (interpret-mode tests use odd sizes)."""
    if dim <= preferred:
        return dim
    b = preferred
    while b >= align:
        if dim % b == 0:
            return b
        b -= align
    return dim
