"""Parameter templates: one declarative tree per model family.

A template is a nested dict whose leaves are ``P`` specs (shape, logical
axes, init law).  From one template we derive:

  * ``abstract(template)``   -> ShapeDtypeStruct tree (dry-run: NO allocation)
  * ``initialize(template)`` -> materialized param tree (training)
  * ``shardings(template)``  -> NamedSharding tree via the logical-axis Rules

keeping shapes, shardings and init in lockstep by construction.
"""
from __future__ import annotations

import zlib
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.sharding import Rules


@dataclass(frozen=True)
class P:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"       # normal | zeros | ones | embed | fanin | neg1
    dtype: str = "float32"
    fan_in: Optional[int] = None   # explicit fan-in for "fanin" init (4D
    #                                weights: shape[-2] is NOT the fan-in)

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _is_leaf(x):
    return isinstance(x, P)


def tree_map(fn, template):
    return jax.tree_util.tree_map(fn, template, is_leaf=_is_leaf)


def abstract(template, rules: Optional[Rules] = None):
    """ShapeDtypeStruct tree; attaches NamedShardings when rules has a mesh."""
    def leaf(p: P):
        sharding = rules.sharding(p.axes, p.shape) if rules and rules.mesh else None
        return jax.ShapeDtypeStruct(p.shape, jnp.dtype(p.dtype), sharding=sharding)
    return tree_map(leaf, template)


def shardings(template, rules: Rules):
    return tree_map(lambda p: rules.sharding(p.axes, p.shape), template)


def specs(template, rules: Rules):
    return tree_map(lambda p: rules.spec(p.axes, p.shape), template)


def _init_leaf(p: P, key):
    if p.init == "zeros":
        return jnp.zeros(p.shape, p.dtype)
    if p.init == "neg1":
        return jnp.full(p.shape, -1, p.dtype)
    if p.init == "ones":
        return jnp.ones(p.shape, p.dtype)
    if p.init == "embed":
        return jax.random.normal(key, p.shape, p.dtype) * 0.02
    if p.init == "fanin":
        fan_in = p.fan_in or (p.shape[-2] if len(p.shape) >= 2
                              else p.shape[-1])
        return jax.random.normal(key, p.shape, p.dtype) / np.sqrt(fan_in)
    if p.init == "normal":
        return jax.random.normal(key, p.shape, p.dtype) * 0.02
    if p.init == "ssm_a":
        # mamba2: A_log init so that -exp(A_log) in [-1, -H]
        row = jnp.log(jnp.arange(1, p.shape[-1] + 1, dtype=p.dtype))
        return jnp.broadcast_to(row, p.shape)
    if p.init == "ssm_dt":
        # dt bias: softplus^-1 of dt in [1e-3, 1e-1], log-uniform
        u = jnp.linspace(np.log(1e-3), np.log(1e-1), num=int(np.prod(p.shape)))
        dt = jnp.exp(u).reshape(p.shape).astype(p.dtype)
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(f"unknown init {p.init!r}")


def initialize(template, rng):
    """Materialize params; per-leaf keys derived from the tree path."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        template, is_leaf=_is_leaf)
    out = []
    for path, p in leaves:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        # crc32, not hash(): str hashes change from process to process,
        # and the same seed must give the same weights in every run
        key = jax.random.fold_in(rng, zlib.crc32(name.encode()) % (2**31))
        out.append(_init_leaf(p, key))
    return jax.tree_util.tree_unflatten(treedef, out)


def count_params(template) -> int:
    leaves = jax.tree_util.tree_leaves(template, is_leaf=_is_leaf)
    return int(sum(np.prod(p.shape) for p in leaves))


def bytes_params(template) -> int:
    leaves = jax.tree_util.tree_leaves(template, is_leaf=_is_leaf)
    return int(sum(np.prod(p.shape) * jnp.dtype(p.dtype).itemsize for p in leaves))
