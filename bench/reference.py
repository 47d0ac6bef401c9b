"""What every kernel's plain reference (``bench/kernels/<kernel>.py``'s
``expected``) shares: 32-bit two's-complement fixed point, in which
every sum and product wraps to 32 bits and a right shift is arithmetic.
The references are written from the paper's description, import nothing
of the program under test, and work on a batch of requests at once
(leading axis).
"""
from __future__ import annotations

import numpy as np


def wrap32(x: np.ndarray) -> np.ndarray:
    """``x`` wrapped to 32-bit two's complement, held in int64 so that
    the next sum or product can be wrapped again."""
    return x.astype(np.int64).astype(np.int32).astype(np.int64)
