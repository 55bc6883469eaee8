"""Inputs of a run, made from seeds on the device.

Series and queries are random walks (cumulative sums of standard normal
steps), the synthetic data and queries of the FreSh and Hydra papers.
The series come from `--seed`; the queries from a seed of the traffic
mix, ordered by `--seed` (`queries`, `order`).  Each use draws from its own
stream, so data and queries never share random numbers.  The same seed
gives the same bits: one jitted program per shape, one call.

A deployment whose series are split into S row shards (`sharded_walks`)
draws block s, rows s * n/S onwards, from a stream of its own,
`fold_in(seed_key(seed, stream), s)`, and makes it on the chip that
holds it, all blocks in one program; `walk_block` makes the same block
again on the default device.
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


def _block_key(seed: int, stream: int, block: int):
    import jax
    return jax.random.fold_in(seed_key(seed, stream), block)


def walk_block(seed: int, stream: int, block: int, rows: int, length: int):
    """Block `block` of a sharded set of walks, (rows, length) float32 on
    the default device, as `sharded_walks` makes it on its own chip."""
    return _walk_fn(rows, length)(_block_key(seed, stream, block))


@functools.lru_cache(maxsize=None)
def _sharded_walk_fn(rows: int, length: int, mesh):
    import jax
    from jax.sharding import PartitionSpec
    spec = PartitionSpec(mesh.axis_names[0])
    gen = _walk_fn(rows, length)
    return jax.jit(jax.shard_map(lambda keys: gen(keys[0]), mesh=mesh,
                                 in_specs=spec, out_specs=spec))


def sharded_walks(seed: int, stream: int, n: int, length: int, mesh):
    """(n, length) float32 random walks, row-sharded over the one axis of
    `mesh`: block s, `walk_block(seed, stream, s, ...)`, made on the
    mesh's device s by one program for the whole mesh, so no device
    ever holds more than its own block."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    S = mesh.devices.size
    keys = jax.device_put(
        jnp.stack([_block_key(seed, stream, s) for s in range(S)]),
        NamedSharding(mesh, PartitionSpec(mesh.axis_names[0])))
    return _sharded_walk_fn(n // S, length, mesh)(keys)


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
