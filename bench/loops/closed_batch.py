"""Closed loop, one caller: back-to-back `FreshIndex.search(batch, k)`
calls, each waited for with `block_until_ready`, on batches of fresh
random-walk queries put on the device in set-up.

Mix parameters: "batch" (queries per call), "k", "pool_batches"
(distinct batches the window walks through; it starts again at the
first only once it has used them all), "order_block", "query_seed"
(`data.queries`).  The search is exact.  A batch's time follows its
hardest query, so every run walks the same batches of the same
queries, each moved by its seed only within its block of
"order_block": a window that ends within block j has searched blocks
0..j-1 whole, for every seed.  Set-up warms up on one batch more, drawn
with the pool and searched by no window.

The window ends when the first call that finishes at or after
`seconds` has finished; `queries_per_s` is every query of the window
over the window's whole length.
"""

from __future__ import annotations

import time

import numpy as np


def prepare(run, seconds: float) -> None:
    from bench import data
    mix = run.cell.mix
    b, p = int(mix["batch"]), int(mix["pool_batches"])
    import jax
    drawn = data.queries(mix, b * (p + 1), run.series_len)
    drawn = drawn.reshape(p + 1, b, -1)
    warm = drawn[p]
    host = drawn[:p][data.order(run.seed, p, int(mix["order_block"]))]
    run.state["pool"] = [jax.device_put(batch) for batch in host]
    run.state["host"] = host.reshape(p * b, -1)
    d, i = run.index.search(jax.device_put(warm), k=int(mix["k"]))
    d.block_until_ready()
    i.block_until_ready()


def measure(run, seconds: float):
    import jax
    from bench.harness import Window
    mix = run.cell.mix
    k, b = int(mix["k"]), int(mix["batch"])
    pool, index = run.state["pool"], run.index
    out = []
    calls = 0
    t0 = time.perf_counter()
    while True:
        j = calls % len(pool)
        with jax.profiler.TraceAnnotation("bench.search"):
            d, i = index.search(pool[j], k=k)
            d.block_until_ready()
            i.block_until_ready()
        out.append((j, d, i))
        calls += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    host = run.state["host"]
    rows = np.concatenate([np.arange(j * b, (j + 1) * b) for j, _, _ in out])
    return Window(
        seconds=elapsed, attempted=calls * b, errors=0,
        metrics={"queries_per_s": calls * b / elapsed},
        queries=host[rows],
        dist=np.concatenate([np.asarray(d).reshape(b, -1) for _, d, _ in out]),
        ids=np.concatenate([np.asarray(i).reshape(b, -1) for _, _, i in out]),
        counters={"calls": calls, "queries": calls * b},
        notes={"calls": calls})


def close(run) -> None:
    run.state.clear()
