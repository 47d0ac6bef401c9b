"""Production mesh definitions.

The mesh axes follow the paper's TLP/DLP decomposition: ``data`` (and
``pod``) carry thread-level parallelism (the IMT harts, scaled out),
``model`` carries data-level parallelism (the vector lanes D, scaled up).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state (smoke tests must keep seeing 1 CPU device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(shape=None, axes=("data", "model")):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if shape is None:
        shape = (n, 1) if len(axes) == 2 else (n,)
    return _auto_mesh(shape, axes)


def _auto_mesh(shape, axes):
    # the models place activations with with_sharding_constraint and let
    # the partitioner propagate the rest, which needs Auto axes
    # (jax.make_mesh defaults to Explicit)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


# TPU v5e-class hardware constants used by the roofline analysis.
HW = {
    "peak_flops_bf16": 197e12,     # per chip
    "hbm_bw": 819e9,               # bytes/s per chip
    "ici_bw": 50e9,                # bytes/s per link
    "hbm_bytes": 16 * 1024**3,     # capacity per chip
}
