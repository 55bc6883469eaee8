"""Serving-layer benchmark: a Poisson open-loop arrival stream driven
through `FreshIndex.engine()` (`benchmarks/run.py --serve-quick`).

Measures what the figures cannot: steady-state serving behaviour —
per-query p50/p99 latency under micro-batching, achieved QPS, plan-cache
hit rate (zero re-traces after warmup is the design claim), padding
overhead, and the one-off cold cost of AOT-compiling the bucket plans.
Rows land in BENCH_fresh.json next to the figure rows (`serve/poisson/
steady`, `serve/warmup_aot_compile`).

Two legs share one Poisson driver:

* local   — the engine over an unsharded index (in-process);
* sharded — the SAME stream through an engine over `index.shard(mesh)`
  (`serve/sharded/warmup_aot_compile`, `serve/sharded/poisson/steady`).
  On an accelerator it runs in-process over the real devices.  On the
  CPU it runs on a forced 2-device host mesh: jax pins the device count
  at first init, so there the leg runs in a SUBPROCESS (`python -m
  benchmarks.serve_bench --sharded-child`) with
  XLA_FLAGS=--xla_force_host_platform_device_count=2 and hands its rows
  back as JSON on stdout.  Read EXPERIMENTS.md §Serving for why sharded
  CPU QPS is a property check, not a speedup claim.

Open-loop means arrivals do NOT wait for completions (the classic
coordinated-omission trap): submission times are scheduled ahead from an
exponential inter-arrival draw and latency is measured from the
*scheduled* arrival, so a stalled engine shows up as a p99 spike instead
of silently throttling the offered load.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import List

import numpy as np

from repro.api import FreshIndex, IndexConfig
from repro.data.synthetic import query_workload, random_walk
from repro.serve import AdmissionError, DeadlineExceeded, EngineConfig

from .common import latency_summary, row

N_SERIES = 4_000
N_QUERIES = 200          # arrival stream length
TARGET_QPS = 400.0
MAX_BATCH = 16
K = 10
QUICK = False
SHARDED_DEVICES = 2
_CHILD_MARK = "SHARDED_ROWS_JSON:"


def set_quick() -> None:
    """Same CI knob as fresh_bench: shrink the stream, keep the shape."""
    global N_SERIES, N_QUERIES, QUICK
    N_SERIES = 2_000
    N_QUERIES = 120
    QUICK = True


def _drive_poisson(eng, queries: np.ndarray, prefix: str,
                   extra_derived: str = "") -> List[dict]:
    """Warmup + Poisson stream through an already-built engine; returns
    the `<prefix>/warmup_aot_compile` and `<prefix>/poisson/steady`
    rows.  One driver for the local and sharded legs so their rows stay
    comparable column for column."""
    out = []
    # cold cost: AOT-compiling every (bucket, k=K) plan up front — the
    # trace+compile work a facade serving loop would pay inline, spread
    # invisibly over its first requests
    t0 = time.perf_counter()
    eng.warmup(ks=(K,))
    t_warm = time.perf_counter() - t0
    n_plans = eng.stats()["plan_cache"]["size"]
    out.append(row(f"{prefix}/warmup_aot_compile", t_warm,
                   f"plans={n_plans} k={K} buckets=pow2..{MAX_BATCH}"
                   + (f" {extra_derived}" if extra_derived else "")))

    rng = np.random.default_rng(43)
    gaps = rng.exponential(1.0 / TARGET_QPS, N_QUERIES)
    qidx = rng.integers(0, queries.shape[0], N_QUERIES)

    # futures stamp completed_at on time.monotonic(); schedule there too
    t_start = time.monotonic()
    sched = t_start
    futs = []
    for g, qi in zip(gaps, qidx):
        sched += g
        now = time.monotonic()
        if sched > now:
            time.sleep(sched - now)
        futs.append((sched, eng.submit(queries[qi], k=K)))
    lat = []
    for sched, f in futs:
        f.result(timeout=300)
        lat.append(f.completed_at - sched)
    wall = time.monotonic() - t_start
    st = eng.stats()
    pc = st["plan_cache"]
    out.append(row(
        f"{prefix}/poisson/steady", wall,
        f"offered={TARGET_QPS:.0f}qps stream={N_QUERIES}"
        + (f" {extra_derived}" if extra_derived else ""),
        qps=round(N_QUERIES / wall, 1),
        **latency_summary(lat),
        rounds_per_query=round(st["rounds_per_query"], 2),
        plan_hits=pc["hits"], plan_misses=pc["misses"],
        padded_slots=st["batches"]["padded_slots"],
        dispatched=st["batches"]["dispatched"]))
    return out


def serve_poisson() -> List[dict]:
    walks = random_walk(N_SERIES, 256, seed=41)
    queries = query_workload(walks, 64, noise_sigma=0.05, seed=42)
    index = FreshIndex.build(walks, IndexConfig(leaf_capacity=64))
    eng = index.engine(EngineConfig(max_batch=MAX_BATCH, workers=1,
                                    linger_ms=1.0, warm_ks=(K,)))
    try:
        return _drive_poisson(eng, queries, "serve")
    finally:
        eng.close()


def _sharded_rows() -> List[dict]:
    """The sharded leg: an engine over the same workload, sharded over
    every device this process sees."""
    import jax
    walks = random_walk(N_SERIES, 256, seed=41)
    queries = query_workload(walks, 64, noise_sigma=0.05, seed=42)
    index = FreshIndex.build(walks, IndexConfig(leaf_capacity=64))
    n_dev = len(jax.devices())
    mesh = jax.make_mesh((n_dev,), ("data",))
    index.shard(mesh)
    eng = index.engine(EngineConfig(max_batch=MAX_BATCH, workers=1,
                                    linger_ms=1.0, warm_ks=(K,),
                                    sync_every=2))
    try:
        return _drive_poisson(eng, queries, "serve/sharded",
                              extra_derived=f"mesh=data:{n_dev}")
    finally:
        eng.close()


def _sharded_child() -> None:
    """Body of the forced-2-device subprocess: prints the sharded rows
    as one marked JSON line."""
    print(_CHILD_MARK + json.dumps(_sharded_rows()), flush=True)


def serve_sharded() -> List[dict]:
    """The sharded leg.  On an accelerator it runs in this process, on
    the real devices: this process already holds them, and a child that
    needs them would fail or hang.  On the CPU it spawns a child under a
    forced multi-device host platform (this process keeps its single
    device — jax pins the count at first init) and adopts its rows."""
    import jax
    if jax.default_backend() != "cpu":
        return _sharded_rows()
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{SHARDED_DEVICES}")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(root, "src")
    env["PYTHONPATH"] = src + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    args = [sys.executable, "-m", "benchmarks.serve_bench",
            "--sharded-child"]
    if QUICK:
        args.append("--quick")
    r = subprocess.run(args, capture_output=True, text=True, env=env,
                       cwd=root, timeout=1800)
    if r.returncode != 0:
        raise RuntimeError(
            f"sharded serve child failed:\nSTDOUT:\n{r.stdout}\n"
            f"STDERR:\n{r.stderr}")
    for line in r.stdout.splitlines():
        if line.startswith(_CHILD_MARK):
            return json.loads(line[len(_CHILD_MARK):])
    raise RuntimeError(f"sharded serve child emitted no rows:\n{r.stdout}")


# --------------------------------------------------------------------- #
# overload sweep: behavior at and past saturation (serve/overload/*)
# --------------------------------------------------------------------- #
OVERLOAD_MULTS = (0.5, 1.0, 2.0, 3.0)


def _closed_loop_qps(eng, queries: np.ndarray, n: int = 96) -> float:
    """Saturation estimate: submit n single-row queries flat out and
    measure completion throughput (full buckets, no idle time)."""
    t0 = time.monotonic()
    futs = [eng.submit(queries[i % queries.shape[0]], k=K)
            for i in range(n)]
    for f in futs:
        f.result(timeout=300)
    return n / (time.monotonic() - t0)


def _drive_overload(eng, queries: np.ndarray, name: str, offered: float,
                    n_arrivals: int, sat: float,
                    deadline_ms=None, seed: int = 47) -> dict:
    """One open-loop Poisson leg at `offered` qps; latency is measured
    from the SCHEDULED arrival (coordinated-omission safe) and only over
    ADMITTED-AND-DELIVERED queries — shed and expired queries are
    reported as rates, not hidden in the tail."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / offered, n_arrivals)
    qidx = rng.integers(0, queries.shape[0], n_arrivals)
    t_start = time.monotonic()
    sched = t_start
    futs, shed = [], 0
    for g, qi in zip(gaps, qidx):
        sched += g
        now = time.monotonic()
        if sched > now:
            time.sleep(sched - now)
        try:
            futs.append((sched, eng.submit(queries[qi], k=K,
                                           deadline_ms=deadline_ms)))
        except AdmissionError:
            shed += 1
    lat, expired = [], 0
    for sched, f in futs:
        try:
            f.result(timeout=300)
            lat.append(f.completed_at - sched)
        except DeadlineExceeded:
            expired += 1
    wall = time.monotonic() - t_start
    st = eng.stats()
    rc = st["result_cache"]
    return row(
        name, wall,
        f"offered={offered:.0f}qps sat={sat:.0f}qps stream={n_arrivals} "
        f"max_pending={eng.config.max_pending} "
        f"deadline_ms={deadline_ms} cache_hits={rc['hits']}",
        goodput_qps=round(len(lat) / wall, 1),
        shed_rate=round(shed / n_arrivals, 3),
        delivered=len(lat), shed=shed, expired=expired,
        **latency_summary(lat))


def serve_overload() -> List[dict]:
    """Offered load 0.5x-3x saturation, three engine configurations:

    * bounded   — max_pending=MAX_BATCH//4 (a quarter bucket of
      headroom) plus a per-query deadline of ~1.2 full-bucket service
      times: goodput and ADMITTED p99 must stay flat past the knee (an
      admitted query can never sit behind more than a few rows of
      backlog, and the deadline clips clock-noise stragglers);
    * unbounded — the pre-admission engine: same stream, queue grows
      without bound past 1x and p99 diverges with offered load;
    * cached    — bounded + the epoch-keyed result cache over the
      repeating 64-query workload: hits bypass the queue entirely.
    """
    walks = random_walk(N_SERIES, 256, seed=41)
    queries = query_workload(walks, 64, noise_sigma=0.05, seed=42)
    index = FreshIndex.build(walks, IndexConfig(leaf_capacity=64))
    base = dict(max_batch=MAX_BATCH, workers=1, linger_ms=1.0,
                warm_ks=(K,))
    plans = None

    def engine(**kw):
        nonlocal plans
        eng = index.engine(EngineConfig(**base, **kw))
        if plans is not None:
            eng.plans = plans        # share AOT plans across legs (same
        eng.warmup(ks=(K,))          # index/epoch -> same plan sigs)
        plans = eng.plans
        return eng

    eng = engine()
    try:
        sat = _closed_loop_qps(eng, queries)
    finally:
        eng.close()
    max_pending = MAX_BATCH // 4
    deadline_ms = round(1.2e3 * MAX_BATCH / sat, 2)  # ~1.2 bucket services

    out: List[dict] = []
    for mult in OVERLOAD_MULTS:
        eng = engine(max_pending=max_pending)
        try:
            out.append(_drive_overload(
                eng, queries, f"serve/overload/bounded/x{mult}",
                sat * mult, N_QUERIES, sat, deadline_ms=deadline_ms))
        finally:
            eng.close()
    for mult in (1.0, 3.0):
        eng = engine()
        try:
            out.append(_drive_overload(
                eng, queries, f"serve/overload/unbounded/x{mult}",
                sat * mult, N_QUERIES, sat))
        finally:
            eng.close()
    eng = engine(max_pending=max_pending, cache_entries=256)
    try:
        out.append(_drive_overload(
            eng, queries, "serve/overload/cached/x3.0",
            sat * 3.0, N_QUERIES, sat, deadline_ms=deadline_ms))
    finally:
        eng.close()
    return out


ALL = [serve_poisson, serve_sharded, serve_overload]


if __name__ == "__main__":
    if "--quick" in sys.argv:
        set_quick()
    if "--sharded-child" in sys.argv:
        _sharded_child()
    else:
        for fn in ALL:
            for r in fn():
                print(r)
