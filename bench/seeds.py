"""Keys and generators from the run's ``--seed``, which may exceed 32 bits."""
from __future__ import annotations

import jax
import numpy as np

# what each stream of a run draws from, so no two share one
CORPUS, WEIGHTS, QUERIES, PROMPTS, TRAFFIC, SAMPLE = range(6)


def key(seed: int, stream: int) -> jax.Array:
    s = int(seed) % (1 << 63)
    k = jax.random.PRNGKey(s & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(k, s >> 32), stream)


def rng(seed: int, stream: int, *more: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed) % (1 << 63), stream, *[int(m) % (1 << 63) for m in more]]))
