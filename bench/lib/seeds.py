"""Keys and generators from a ``--seed`` of any size."""
from __future__ import annotations

import numpy as np


def jax_key(seed: int, *path: int):
    """A JAX PRNG key from all 64 bits of ``seed`` (``PRNGKey`` keeps 32)."""
    import jax

    seed %= 2 ** 64
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)
    for p in path:
        key = jax.random.fold_in(key, p)
    return key


def np_rng(seed: int, *path: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2 ** 64, *path])
