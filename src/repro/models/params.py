"""Parameter schema: declarative param trees with logical sharding axes.

Every model declares its parameters as a nested dict of ``ParamDef``s.
From one schema we derive:

  * ``init_tree``     — materialised arrays (seeded, for real runs)
  * ``abstract_tree`` — ShapeDtypeStructs (for the dry-run; no allocation)
  * ``axes_tree``     — logical-axis tuples per leaf (for sharding rules)

``empty_cache`` materialises a model's ``cache_spec`` the same way: every
serving cache (a fresh contiguous one, the paged pool) starts empty.

Logical axis names (mapped to mesh axes by repro.dist.sharding.Rules):
  layers, d_model, ffn, heads, kv_heads, head_dim, vocab, experts, lora,
  state, conv, frames, norm (never sharded), stack (scan groups)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    dtype: jnp.dtype = jnp.bfloat16
    init: str = "normal"      # normal | zeros | ones | embed
    scale: float = 1.0        # fan-in override multiplier

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


def _init_leaf(key, d: ParamDef):
    if d.init == "zeros":
        return jnp.zeros(d.shape, d.dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, d.dtype)
    fan_in = d.shape[-2] if len(d.shape) >= 2 else d.shape[-1]
    if d.init == "embed":
        std = 1.0
    else:
        std = d.scale / math.sqrt(max(1, fan_in))
    return (jax.random.normal(key, d.shape, jnp.float32) * std).astype(d.dtype)


def init_tree(schema, key):
    leaves, treedef = jax.tree.flatten(schema, is_leaf=is_def)
    keys = jax.random.split(key, len(leaves))
    vals = [_init_leaf(k, d) for k, d in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, vals)


def abstract_tree(schema):
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype), schema, is_leaf=is_def)


def axes_tree(schema):
    return jax.tree.map(lambda d: d.axes, schema, is_leaf=is_def)


def empty_value(dtype) -> int:
    """What an empty cache leaf holds: -1 for int32 positions, else 0."""
    return -1 if dtype == jnp.int32 else 0


def empty_cache(spec):
    """A fresh cache from a ``cache_spec`` tree of ShapeDtypeStructs."""
    return jax.tree.map(
        lambda s: jnp.full(s.shape, empty_value(s.dtype), s.dtype), spec)


def stack(schema, n: int, axis_name: str = "stack"):
    """Prepend a stacking (scan) dimension to every ParamDef in a subtree."""
    return jax.tree.map(
        lambda d: ParamDef((n,) + d.shape, (axis_name,) + d.axes, d.dtype,
                           d.init, d.scale),
        schema, is_leaf=is_def)


def param_count(schema) -> int:
    leaves = jax.tree.leaves(schema, is_leaf=is_def)
    return sum(int(np.prod(d.shape)) for d in leaves)
