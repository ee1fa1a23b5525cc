"""Counter-based token sampling, shared by the serving engine and the
tests' reference.

Temperature sampling is keyed on ``(seed, rid, step)`` via
``jax.random.fold_in`` + ``jax.random.categorical``: the token a request
samples at step *t* is a pure function of its own logits and identity.
That makes sampled streams bit-stable across runs and batch
compositions — which slot a request lands in, or which neighbours share
its decode batch, cannot perturb its randomness.

The alternative this replaces (a shared ``np.random.Generator`` consumed
in batch order, with a float64 softmax renormalisation before
``rng.choice``) had neither property: retiring a neighbour reordered the
stream consumption, and the renormalisation was platform-fragile.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def sample_row(logits_row, *, seed: int, rid: int, step: int,
               temperature: float) -> int:
    """Sample one token for request ``rid`` at output step ``step``."""
    if temperature <= 0:
        return int(jnp.argmax(logits_row))
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.key(seed), rid), step)
    scaled = jnp.asarray(logits_row, jnp.float32) / temperature
    return int(jax.random.categorical(key, scaled))


def sample_tokens(logits, rows: Sequence[Optional[tuple]], *, seed: int,
                  temperature: float) -> np.ndarray:
    """Per-row sampling for a batch of logits.

    ``rows[i]`` is ``(rid, step)`` for a live row, or ``None`` for a dead
    / padding row (its output is an argmax placeholder the caller
    discards — dead rows must not consume or perturb any randomness).
    """
    greedy = np.asarray(jnp.argmax(logits, -1), np.int32)
    if temperature <= 0:
        return greedy
    out = greedy.copy()
    for i, row in enumerate(rows):
        if row is None:
            continue
        rid, step = row
        out[i] = sample_row(logits[i], seed=seed, rid=rid, step=step,
                            temperature=temperature)
    return out
