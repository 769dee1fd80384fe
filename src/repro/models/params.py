"""Parameter definition machinery.

Every model module declares its parameters once as a tree of ``ParamDef``
(shape + logical axis names + initializer). From that single declaration we
derive:

  * real initialization (smoke tests / examples, tiny configs),
  * abstract ``ShapeDtypeStruct`` trees with ``NamedSharding`` for the
    multi-pod dry-run (no allocation — mandatory for the 671B config),
  * pjit ``in_shardings`` via the logical-axis → mesh-axis rules in
    ``repro.sharding.rules``.

This is the MaxText-style "logical axis annotation" pattern, kept minimal.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp

Pytree = Any


@dataclasses.dataclass(frozen=True)
class ParamDef:
    """Declaration of a single parameter tensor."""

    shape: tuple[int, ...]
    logical: tuple[str | None, ...]  # one logical axis name per dim
    init: str = "fan_in"  # fan_in | zeros | ones | normal | embed | scalar_log
    dtype: Any = jnp.bfloat16
    scale: float = 1.0  # extra multiplier for normal init

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


@functools.partial(jax.jit, static_argnums=1)
def _initialize(key: jax.Array, d: ParamDef) -> jax.Array:
    """Draw one leaf. Jitted, so the f32 draw fuses into the cast and the
    device never holds a full-size f32 copy of a bf16 leaf."""
    if d.init == "zeros":
        return jnp.zeros(d.shape, d.dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, d.dtype)
    if d.init == "scalar_log":  # e.g. Mamba A_log, init in [1, 16)
        u = jax.random.uniform(key, d.shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u).astype(d.dtype)
    if d.init == "embed":
        x = jax.random.normal(key, d.shape, jnp.float32) * d.scale
        return x.astype(d.dtype)
    if d.init == "normal":
        x = jax.random.normal(key, d.shape, jnp.float32) * 0.02 * d.scale
        return x.astype(d.dtype)
    # fan_in (truncated-normal-ish): std = 1/sqrt(fan_in), fan_in = first dim
    fan_in = d.shape[0] if len(d.shape) >= 2 else max(d.shape[0], 1)
    if len(d.shape) >= 3:  # stacked-over-layers leading dim is not fan-in
        fan_in = d.shape[-2]
    std = d.scale / math.sqrt(max(fan_in, 1))
    x = jax.random.normal(key, d.shape, jnp.float32) * std
    return x.astype(d.dtype)


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


def init_params(defs: Pytree, key: jax.Array) -> Pytree:
    """Materialize a ParamDef tree into real arrays (small configs only)."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=is_def)
    keys = jax.random.split(key, len(leaves))
    out = [_initialize(k, d) for k, d in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, out)


def abstract_params(defs: Pytree, sharding_fn: Callable[[ParamDef], Any] | None = None) -> Pytree:
    """ShapeDtypeStruct tree (optionally with shardings) — zero allocation."""

    def mk(d: ParamDef):
        if sharding_fn is None:
            return jax.ShapeDtypeStruct(d.shape, d.dtype)
        return jax.ShapeDtypeStruct(d.shape, d.dtype, sharding=sharding_fn(d))

    return jax.tree.map(mk, defs, is_leaf=is_def)


def param_specs(defs: Pytree, spec_fn: Callable[[ParamDef], Any]) -> Pytree:
    """PartitionSpec tree matching the ParamDef tree."""
    return jax.tree.map(spec_fn, defs, is_leaf=is_def)


def count_params(defs: Pytree) -> int:
    leaves = jax.tree.leaves(defs, is_leaf=is_def)
    return sum(math.prod(d.shape) for d in leaves)


def stacked(n: int, defs: Pytree) -> Pytree:
    """Prepend a scan ('layers') dimension to every ParamDef in a subtree."""

    def add(d: ParamDef) -> ParamDef:
        return dataclasses.replace(d, shape=(n, *d.shape), logical=("layers", *d.logical))

    return jax.tree.map(add, defs, is_leaf=is_def)
