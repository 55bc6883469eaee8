"""Serving quickstart: the QueryEngine in 60 lines.

Builds an index, AOT-warms the per-bucket search plans, serves a stream
of micro-batched k-NN submits (zero re-traces in steady state), then
inserts a batch mid-stream to show Jiffy-style snapshot consistency: the
in-flight future answers on the pre-insert snapshot while the next one
sees the new series.  A final overload leg shows graceful degradation:
bounded admission sheds with a typed AdmissionError, per-query deadlines
expire with DeadlineExceeded, and the epoch-keyed result cache answers a
repeated query without touching the batcher at all.

    PYTHONPATH=src python examples/serve_engine.py
"""

import time

import numpy as np

from repro.api import FreshIndex, IndexConfig
from repro.serve import (AdmissionError, DeadlineExceeded, EngineConfig)
from repro.data.synthetic import query_workload, random_walk
from repro.launch.compile_cache import use_compile_cache

use_compile_cache()
N, L, K = 20_000, 256, 10

print(f"building a FreSh index over {N} series ...")
walks = random_walk(N, L, seed=0)
queries = query_workload(walks, 64, noise_sigma=0.05, seed=1)
index = FreshIndex.build(walks, IndexConfig(leaf_capacity=64))

with index.engine(EngineConfig(max_batch=16, workers=1,
                               linger_ms=1.0)) as engine:
    print("AOT-compiling the bucket plans (warmup) ...")
    t0 = time.time()
    engine.warmup(ks=(K,))
    print(f"  {engine.stats()['plan_cache']['size']} plans "
          f"in {time.time()-t0:.2f}s")

    print("serving 100 submits through the micro-batcher ...")
    futs = [engine.submit(queries[i % 64], k=K) for i in range(100)]
    results = [f.result(timeout=120) for f in futs]
    st = engine.stats()
    print(f"  p50={st['latency_ms']['p50']:.2f}ms "
          f"p99={st['latency_ms']['p99']:.2f}ms "
          f"qps={st['qps']:.0f} "
          f"plan hits/misses={st['plan_cache']['hits']}"
          f"/{st['plan_cache']['misses']} "
          f"rounds/query={st['rounds_per_query']:.1f}")
    assert st["plan_cache"]["misses"] == st["plan_cache"]["size"], \
        "steady state must not re-trace"

    print("concurrent insert: snapshot consistency ...")
    inflight = engine.submit(queries[:8], k=1)       # epoch 0
    engine.add(random_walk(500, L, seed=2))          # publish epoch 1
    later = engine.submit(queries[:8], k=1)          # sees the new series
    d_old, i_old = inflight.result(timeout=120)
    d_new, i_new = later.result(timeout=120)
    assert np.all(i_old < N), "in-flight answered on the pre-add snapshot"
    print(f"  epoch={engine.epoch}: in-flight ids stayed < {N} (its "
          f"submit-time snapshot); the later submit searched all "
          f"{index.n_series} series")

print("overload: admission control, deadlines, result cache ...")
with index.engine(EngineConfig(max_batch=16, workers=0,  # manual drain:
                               linger_ms=0.0,            # queue stays put
                               max_pending=4,            # until we flush
                               cache_entries=64)) as engine:
    # 1) bounded admission: the 4-row budget admits one 4-row submit,
    #    then sheds the next one with a typed error instead of queueing
    admitted = engine.submit(queries[:4], k=K)
    try:
        engine.submit(queries[4:8], k=K)
        raise AssertionError("expected the 5th pending row to shed")
    except AdmissionError as e:
        print(f"  shed:     AdmissionError: {e}")

    engine.flush()                       # drain the admitted queries
    d_cold, i_cold = admitted.result(timeout=10)

    # 2) deadline: an expired query fails typed at form time — it is
    #    never silently delivered late
    doomed = engine.submit(queries[8], k=K, deadline_ms=0.001)
    time.sleep(0.01)
    engine.flush()
    try:
        doomed.result(timeout=10)
        raise AssertionError("expected the expired query to fail")
    except DeadlineExceeded as e:
        print(f"  deadline: DeadlineExceeded: {e}")

    # 3) result cache: resubmitting the same queries on the same epoch
    #    is answered from the cache — bit-identical, no batch formed
    hit = engine.submit(queries[:4], k=K)
    d_hot, i_hot = hit.result(timeout=10)
    assert hit.done() and np.array_equal(d_cold, d_hot) \
        and np.array_equal(i_cold, i_hot), "cache hit must be bit-identical"
    ov, rc = engine.stats()["overload"], engine.stats()["result_cache"]
    print(f"  cache:    {rc['hits']} hits / {rc['fills']} fills — "
          f"bit-identical to the cold pass; "
          f"shed={ov['shed']} expired={ov['deadline_expired']}")

print("OK — micro-batched serving, AOT plans, snapshot-consistent adds, "
      "typed overload degradation.")
