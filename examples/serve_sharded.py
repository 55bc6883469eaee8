"""Sharded serving quickstart: mesh-wide epochs + elastic recovery.

Forces a 2-device host CPU mesh (XLA_FLAGS must be set before jax
imports), block-shards the index over it, and walks the whole sharded
serving story end to end:

* per-(bucket, k, mesh placement) AOT plans — zero re-traces in steady
  state, `submit().result()` bit-identical to `FreshIndex.search` on
  the sharded index;
* a mid-stream insert publishing a MESH-WIDE epoch snapshot (the
  in-flight future answers pre-add, the next one sees the new series);
* a dispatch-worker crash mid-batch — the orphaned shard batch is
  re-executed through the WorkJournal helping path, the future fills;
* a simulated PERMANENT shard loss: save a checkpoint, recover() onto
  the surviving 1-device mesh — the future submitted before the
  recovery still completes.

    PYTHONPATH=src python examples/serve_sharded.py
"""

import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=2")

import tempfile
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.api import FreshIndex, IndexConfig
from repro.core.refresh import WorkerCrash
from repro.data.synthetic import query_workload, random_walk
from repro.launch.compile_cache import use_compile_cache
from repro.serve import EngineConfig

use_compile_cache()
N, L, K = 8_000, 256, 10

n_dev = len(jax.devices())
print(f"building a FreSh index over {N} series; sharding over "
      f"{n_dev} host devices ...")
walks = random_walk(N, L, seed=0)
queries = query_workload(walks, 32, noise_sigma=0.05, seed=1)
index = FreshIndex.build(walks, IndexConfig(leaf_capacity=64))
mesh = jax.make_mesh((n_dev,), ("data",))
index.shard(mesh)

engine = index.engine(EngineConfig(max_batch=8, workers=1, linger_ms=1.0,
                                   sync_every=2, help_after_ms=500.0))
try:
    print("AOT-compiling the per-(bucket, k, mesh) plans ...")
    t0 = time.time()
    engine.warmup(ks=(K,))
    st = engine.stats()
    print(f"  {st['plan_cache']['size']} plans in {time.time()-t0:.2f}s "
          f"on mesh {st['mesh']}")

    print("serving 50 submits through the micro-batcher ...")
    futs = [engine.submit(queries[i % 32], k=K) for i in range(50)]
    for f in futs:
        f.result(timeout=300)
    st = engine.stats()
    assert st["plan_cache"]["misses"] == st["plan_cache"]["size"], \
        "steady state must not re-trace"
    print(f"  p50={st['latency_ms']['p50']:.2f}ms "
          f"p99={st['latency_ms']['p99']:.2f}ms qps={st['qps']:.0f} "
          f"plan hits/misses={st['plan_cache']['hits']}"
          f"/{st['plan_cache']['misses']}")

    d, i = engine.submit(queries[:4], k=K).result(timeout=300)
    df, if_ = index.search(jnp.asarray(queries[:4]), k=K, sync_every=2)
    assert np.array_equal(np.asarray(i), np.asarray(if_))
    assert np.array_equal(np.asarray(d), np.asarray(df))
    print("  bit-identical to FreshIndex.search on the sharded index")

    print("concurrent insert: MESH-WIDE epoch snapshot ...")
    inflight = engine.submit(queries[:8], k=1)       # epoch e
    engine.add(random_walk(500, L, seed=2))          # publish e+1
    later = engine.submit(queries[:8], k=1)
    d_old, i_old = inflight.result(timeout=300)
    later.result(timeout=300)
    assert np.all(i_old < N), "in-flight answered on the pre-add snapshot"
    print(f"  epoch={engine.epoch}: in-flight ids stayed < {N}; the "
          f"later submit searched all {index.n_series} series")

    print("killing the dispatch worker mid-batch ...")
    crashed = []
    def hook(wid, batch):
        # only the real dispatch worker (id 0) crashes, and only once —
        # helpers (huge HELPER_ID) re-executing the orphan must survive
        if wid == 0 and not crashed:
            crashed.append(wid)
            raise WorkerCrash()
    engine._crash_hook = hook
    d, i = engine.submit(queries[:3], k=K).result(timeout=300)
    st = engine.stats()
    print(f"  crashed={st['workers']['crashed']} "
          f"helped={st['batches']['helped']} — the future filled anyway "
          f"(journal helping)")

    print("simulated permanent shard loss: checkpoint + recover() ...")
    ckpt = tempfile.mkdtemp(prefix="fresh-ckpt-")
    index.save(ckpt)
    pending = engine.submit(queries[:5], k=K)        # spans the recovery
    survivors = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    engine.recover(ckpt, mesh=survivors)
    after = engine.submit(queries[:5], k=K)
    d1, i1 = pending.result(timeout=300)
    d2, i2 = after.result(timeout=300)
    assert np.array_equal(i1, i2), "recovery must not change answers"
    st = engine.stats()
    print(f"  recoveries={st['recoveries']}, now serving from mesh "
          f"{st['mesh']}; the in-flight future completed across it")
finally:
    engine.close()

print("OK — sharded AOT plans, mesh-wide epochs, helping, elastic "
      "recovery.")
