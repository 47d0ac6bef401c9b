"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.
A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict


@dataclass(frozen=True)
class Peak:
    bf16_flops: float            # FLOP/s
    int8_ops: float              # OP/s
    hbm_bytes_per_s: float       # B/s
    hbm_bytes: float             # B
    source: str


_V5E = Peak(bf16_flops=197e12, int8_ops=393e12, hbm_bytes_per_s=819e9,
            hbm_bytes=16e9, source="Google Cloud documentation, TPU v5e")

PEAKS: Dict[str, Peak] = {
    "TPU v5 lite": _V5E,         # what JAX reports for a v5e chip
    "TPU v5e": _V5E,
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
