"""Open loop: Poisson arrivals of one query each, through the serving
engine, `QueryEngine.submit(q, k).result()`.

Mix parameters: "rate_per_s" (offered load), "k", "engine" (the
EngineConfig fields the cell sets), "arrival_seed", "query_seed".

Every run of a given length offers the same set of round(rate x
seconds) inter-arrival gaps, drawn once from "arrival_seed" and scaled
to fill the window, and the same queries (`data.queries`); `--seed`
orders both.  So a seed changes which query comes when, never how much
load there is.
One thread submits on the schedule; another waits for the results in
order.  A request's latency runs from its scheduled arrival to the
return of its `result()`; one that raises, or has not come a minute
after the window closed, is an error.

Metrics: `latency_p50_ms` and `latency_p95_ms` over every request, and
`served_queries_per_s`: the results returned from the start of the
window until the first result that returns at or after `seconds`, over
that span.  Above the rate the engine sustains, the queue grows all
through the window and that rate is what the engine serves.
"""

from __future__ import annotations

import queue
import threading
import time

import numpy as np

#: how long after the window a result may still come
GRACE_S = 60.0


def arrivals(mix: dict, seed: int, seconds: float) -> np.ndarray:
    """Offsets in seconds, ascending, of every request of the window."""
    from bench import data
    n = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
    gaps = np.random.default_rng(int(mix["arrival_seed"])).exponential(
        1.0, n + 1)
    gaps *= seconds / gaps.sum()
    return np.cumsum(gaps[data.order(seed, n + 1)][:n])


def _counters(engine) -> dict:
    st = engine.stats()
    return {"completed": st["completed"],
            "dispatched": st["batches"]["dispatched"],
            "padded_slots": st["batches"]["padded_slots"],
            "rounds_sum": st["rounds_per_query"] * st["completed"]}


def prepare(run, seconds: float) -> None:
    from bench import data
    from repro.serve import EngineConfig
    mix = run.cell.mix
    cfg = dict(mix["engine"])
    if "warm_ks" in cfg:
        cfg["warm_ks"] = tuple(cfg["warm_ks"])
    offsets = arrivals(mix, run.seed, seconds)
    n = offsets.shape[0]
    pool = data.queries(mix, n, run.series_len)[data.order(run.seed, n)]
    engine = run.index.engine(EngineConfig(**cfg))
    run.state.update(engine=engine, offsets=offsets, pool=pool)
    engine.warmup()
    engine.submit(pool[0], k=int(mix["k"])).result()


def measure(run, seconds: float):
    import jax
    from bench.harness import Window
    mix, engine, k = run.cell.mix, run.state["engine"], int(run.cell.mix["k"])
    offsets, pool = run.state["offsets"], run.state["pool"]
    n = offsets.shape[0]
    dist = np.zeros((n, k), np.float32)
    ids = np.full((n, k), -1, np.int32)
    lat = np.full(n, np.inf)
    done_at = np.full(n, np.inf)
    late = np.zeros(n)
    errors = [0]
    todo: "queue.Queue" = queue.Queue()
    c0 = _counters(engine)
    t0 = time.perf_counter() + 0.01
    deadline = t0 + seconds + GRACE_S

    def collect():
        for _ in range(n):
            j, fut = todo.get()
            try:
                with jax.profiler.TraceAnnotation("bench.result"):
                    d, i = fut.result(
                        timeout=max(0.0, deadline - time.perf_counter()))
            except Exception:  # noqa: BLE001 - a failed request, counted
                errors[0] += 1
                continue
            now = time.perf_counter()
            lat[j] = now - (t0 + offsets[j])
            done_at[j] = now - t0
            dist[j], ids[j] = np.asarray(d).reshape(-1), np.asarray(
                i).reshape(-1)

    collector = threading.Thread(target=collect, name="bench-collector")
    collector.start()
    for j in range(n):
        due = t0 + offsets[j]
        wait = due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        late[j] = time.perf_counter() - due
        with jax.profiler.TraceAnnotation("bench.submit"):
            fut = engine.submit(pool[j], k=k)
        todo.put((j, fut))
    collector.join(timeout=seconds + 2 * GRACE_S)
    if collector.is_alive():
        raise RuntimeError("the result collector did not finish")
    c1 = _counters(engine)
    done = np.isfinite(lat)
    ms = lat[done] * 1e3
    diff = {key: c1[key] - c0[key] for key in c0}
    # requests still open when the last arrival was due: the backlog
    finish = offsets + lat
    backlog = int((finish > offsets[-1]).sum()) - 1
    after = done_at[done_at >= seconds]
    end = after.min() if after.size else done_at[done].max()
    served = int((done_at <= end).sum())
    return Window(
        seconds=float(done_at[done].max()), attempted=n, errors=errors[0],
        metrics={"latency_p50_ms": float(np.percentile(ms, 50)),
                 "latency_p95_ms": float(np.percentile(ms, 95)),
                 "served_queries_per_s": served / end},
        queries=pool[done], dist=dist[done], ids=ids[done],
        counters=diff,
        notes={"requests": n, "batches": diff["dispatched"],
               "backlog_at_last_arrival": backlog,
               "drain_s": float(finish[done].max() - offsets[-1]),
               "late_p95_ms": float(np.percentile(late, 95) * 1e3),
               "late_max_ms": float(late.max() * 1e3)})


def close(run) -> None:
    engine = run.state.pop("engine", None)
    if engine is not None:
        engine.close()
    run.state.clear()
