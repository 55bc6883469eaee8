"""Inputs of a run, made from seeds on the device.

Series and queries are random walks (cumulative sums of standard normal
steps), the synthetic data and queries of the FreSh and Hydra papers.
The series come from `--seed`; the queries from a seed of the traffic
mix, ordered by `--seed` (`queries`, `order`).  Each use draws from its own
stream, so data and queries never share random numbers.  The same seed
gives the same bits: one jitted program per shape, one call.
"""

from __future__ import annotations

import functools

import numpy as np

#: stream ids folded into the seed's key
DATA, QUERIES = 0, 1


def seed_key(seed: int, stream: int):
    """A PRNG key for `stream` of `seed`.  Seeds above 32 bits keep
    their high word: `PRNGKey` alone would drop it."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


@functools.lru_cache(maxsize=None)
def _walk_fn(n: int, length: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gen(key):
        return jnp.cumsum(jax.random.normal(key, (n, length), jnp.float32),
                          axis=1)
    return gen


def walks(seed: int, stream: int, n: int, length: int):
    """(n, length) float32 random walks on the default device."""
    return _walk_fn(n, length)(seed_key(seed, stream))


def queries(mix: dict, n: int, length: int) -> np.ndarray:
    """(n, length) host queries: one fixed set of fresh walks, drawn
    from the mix's "query_seed", so that every run offers the same work
    and its seed only orders it (the series still come from the seed)."""
    return np.asarray(walks(int(mix["query_seed"]), QUERIES, n, length))


def order(seed: int, n: int, block: int = 0) -> np.ndarray:
    """A permutation of range(n) drawn from `seed`; with `block`, one
    that moves each item only within its run of `block` consecutive
    items, so that any prefix of whole blocks holds the same items for
    every seed."""
    rng = np.random.default_rng(seed)
    if not block:
        return rng.permutation(n)
    return np.concatenate([s + rng.permutation(min(block, n - s))
                           for s in range(0, n, block)])
