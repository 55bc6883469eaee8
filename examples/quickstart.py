"""Quickstart: the paper's system in 30 lines, through the FreshIndex facade.

Builds a FreSh index over 100k random-walk series (the paper's Random
dataset), answers 100 exact 10-NN queries, verifies exactness against the
brute-force oracle, then demonstrates the rest of the lifecycle:
incremental add -> compact, and save -> load.

    PYTHONPATH=src python examples/quickstart.py
"""

import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import FreshIndex, IndexConfig
from repro.core import search_bruteforce
from repro.data.synthetic import query_workload, random_walk
from repro.launch.compile_cache import use_compile_cache

use_compile_cache()
N, L, Q, K = 100_000, 256, 100, 10

print(f"generating {N} random-walk series of length {L} ...")
walks = random_walk(N, L, seed=0)
queries = query_workload(walks, Q, noise_sigma=0.05, seed=1)

print("building the FreSh index (summarize -> sort -> leaves) ...")
t0 = time.time()
index = FreshIndex.build(walks, IndexConfig(leaf_capacity=64))
jax.block_until_ready(index.index.series)
print(f"  built in {time.time()-t0:.2f}s: {index.stats()}")

print(f"answering {Q} exact {K}-NN queries ...")
t0 = time.time()
dist, ids = index.search(queries, k=K)
jax.block_until_ready(dist)
dt = time.time() - t0
print(f"  {dt:.3f}s ({dt/Q*1e3:.2f} ms/query)")

print("verifying exactness against brute force ...")
bf_dist, bf_ids = search_bruteforce(jnp.asarray(walks),
                                    jnp.asarray(queries), k=K)
match = np.mean(np.asarray(ids) == np.asarray(bf_ids))
err = np.max(np.abs(np.asarray(dist) - np.asarray(bf_dist)))
print(f"  id match: {match*100:.1f}%  max |dist err|: {err:.2e}")
assert err < 1e-3

print("streaming multi-worker build (IndexBuilder, 4 lock-free workers) ...")
t0 = time.time()
b = FreshIndex.builder(IndexConfig(leaf_capacity=64), workers=4,
                       part_rows=N // 16)
for lo in range(0, 32_768, 8_192):        # feed a prefix in 4 chunks
    b.feed(walks[lo:lo + 8_192])
streamed = b.finalize()
jax.block_until_ready(streamed.index.series)
oneshot = FreshIndex.build(walks[:32_768], IndexConfig(leaf_capacity=64))
assert np.array_equal(np.asarray(streamed.index.perm),
                      np.asarray(oneshot.index.perm))
helped = sum(p["helped_parts"] for p in b.report()["phases"].values())
print(f"  built {streamed.n_series} series in {time.time()-t0:.2f}s, "
      f"bit-identical to one-shot (helped parts: {helped})")

print("incremental add (Jiffy-style delta) -> compact ...")
fresh_batch = random_walk(1_000, L, seed=2)
index.add(fresh_batch)                    # searchable immediately
d2, i2 = index.search(queries, k=1)
index.compact()                           # incremental sorted-run merge
d3, i3 = index.search(queries, k=1)
assert np.array_equal(np.asarray(i2), np.asarray(i3))
print(f"  {index.stats()['n_series']} series after compact, answers stable")

print("save -> load round trip (no rebuild) ...")
with tempfile.TemporaryDirectory() as ckdir:
    index.save(ckdir)
    restored = FreshIndex.load(ckdir)
    d4, i4 = restored.search(queries, k=K)
assert np.array_equal(np.asarray(i4)[:, 0], np.asarray(i3))
print("OK — exact answers, paper-faithful pipeline, one facade.")
