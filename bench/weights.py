"""Random weights for a model under test, made by the benchmark from the
seed: one jitted call on the device, in the dtype the model serves them.

The tree's structure and leaf shapes come from the model's schema (its
parameter interface); the values are the benchmark's own, so the plain
reference and the program read the same arrays and neither made them.
Norm gains are zero (the models apply ``1 + gain``), the embedding is
standard normal, every other matrix normal with standard deviation
``1 / sqrt(fan_in)``.
"""
from __future__ import annotations

import math
from typing import Any, List, Tuple

import numpy as np


def key_for(seed: int, stream: int):
    """A JAX key from a seed of any size (more than 32 bits)."""
    import jax

    word = np.random.SeedSequence([seed, stream]).generate_state(1)[0]
    return jax.random.key(int(word))


def _leaf_rule(path: Tuple[str, ...], shape: Tuple[int, ...]) -> Tuple[str, float]:
    name = path[-1]
    if "norm" in name or name in ("ln1", "ln2"):
        return "zeros", 0.0
    if name == "embed":
        return "normal", 1.0
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    return "normal", 1.0 / math.sqrt(max(1, fan_in))


def make_params(schema: Any, seed: int):
    """The tree of ``schema`` (leaves with ``shape`` and ``dtype``) filled
    from ``seed`` in one jitted call."""
    import jax
    import jax.numpy as jnp

    flat, treedef = jax.tree_util.tree_flatten_with_path(
        schema, is_leaf=lambda x: hasattr(x, "shape") and hasattr(x, "dtype"))
    specs: List[Tuple[str, float, tuple, Any]] = []
    for path, leaf in flat:
        keys = tuple(str(getattr(p, "key", p)) for p in path)
        kind, std = _leaf_rule(keys, tuple(leaf.shape))
        specs.append((kind, std, tuple(leaf.shape), jnp.dtype(leaf.dtype)))

    @jax.jit
    def make(key):
        out = []
        for i, (kind, std, shape, dtype) in enumerate(specs):
            if kind == "zeros":
                out.append(jnp.zeros(shape, dtype))
            else:
                k = jax.random.fold_in(key, i)
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * std).astype(dtype))
        return out

    leaves = make(key_for(seed, 0))
    return jax.tree_util.tree_unflatten(treedef, leaves)
