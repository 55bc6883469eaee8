#!/usr/bin/env python3
"""On-chip smoke run: the index's main path on a TPU, through the entry
points a user calls, at a size a data-series index is deployed at.

One chip (the default):

    python chip_smoke.py

  data       2^22 random-walk series of length 256, float32, made on the
             device from --seed (4 GiB: a quarter of a v5e's HBM), plus
             128 fresh random-walk queries
  build      FreshIndex.build(series, IndexConfig(backend="pallas"))
  search     index.search(q, k) at k = 1, 10, 100 on the pallas and the
             ref backends, each checked against a chunked brute-force
             scan (search_bruteforce) of the same data
  serve      index.engine(...): warmup, then a few hundred
             submit(q, k).result() calls, bit-identical to index.search,
             no compile after warmup, and the served plan holds a Mosaic
             kernel (tpu_custom_call)
  lifecycle  add 65,536 series, delete 1,024 ids, compact(), searching
             and checking against the alive-masked oracle after each

Four chips:

    python chip_smoke.py --chips 4

  runs only the sharded phase: 2^22 series per chip (built on the host,
  block-sharded over a ("data",) mesh of the four chips), facade search
  and engine submit at k = 10, checked against the single-device oracle,
  and every device holding its share of the index.

Each phase prints its wall time (compile time apart) and the device's
peak_bytes_in_use.  The last line of output is one JSON object,
{"ok": true, "device": {...}}.  Without a TPU the script exits non-zero
before any work, and any failed check raises.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

L = 256
N_QUERIES = 128
KS = (1, 10, 100)
N_ADD = 1 << 16
N_DELETE = 1024
N_SUBMITS = 256
ORACLE_CHUNK = 1 << 19
#: |d^2 - d'^2| below which two candidates count as tied: the index and
#: the oracle select in matmul form (f32, ~1e-4 absolute cancellation
#: error at L=256) and may order a tie differently; every other
#: difference is a wrong answer
TIE_D2 = 1e-3


def log(msg: str) -> None:
    print(msg, flush=True)


# --------------------------------------------------------------------- #
# timing: wall time of a phase, with JAX's compile time reported apart
# --------------------------------------------------------------------- #
class CompileClock:
    """Sums the XLA backend compile durations JAX reports (every
    thread); tracing and lowering stay in the phase's other time."""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.total += duration


class Phase:
    """`with Phase(name, clock, devices):` prints the phase's wall time,
    the compile time inside it, and each device's HBM peak so far."""

    def __init__(self, name: str, clock: CompileClock, devices):
        self.name, self.clock, self.devices = name, clock, devices

    def __enter__(self):
        self.c0 = self.clock.total
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            return False
        wall = time.perf_counter() - self.t0
        comp = self.clock.total - self.c0
        stats = [d.memory_stats() or {} for d in self.devices]
        peak = [s.get("peak_bytes_in_use") for s in stats]
        now = [s.get("bytes_in_use") for s in stats]
        log(f"phase {self.name}: wall_s={wall:.3f} "
            f"xla_compile_s={comp:.3f} rest_s={wall - comp:.3f} "
            f"peak_bytes_in_use={peak} bytes_in_use={now}")
        return False


# --------------------------------------------------------------------- #
# data and the independent oracle
# --------------------------------------------------------------------- #
def walks(seed: int, stream: int, n: int):
    """(n, L) float32 random walks made on the default device."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=(1,))
    def gen(key, n):
        return jnp.cumsum(jax.random.normal(key, (n, L), jnp.float32),
                          axis=1)

    key = jax.random.fold_in(jax.random.PRNGKey(seed), stream)
    return gen(key, n)


def oracle(parts, queries, k: int, dead=frozenset()):
    """Exact top-k over the series in `parts` — a list of (array, id0)
    whose row r holds series id id0 + r — scanned in ORACLE_CHUNK-row
    chunks by `search_bruteforce` (alive-masked where `dead` holds ids)
    and merged by distance.  Returns host (Q, k) distances and ids."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import search_bruteforce

    best_d = best_i = None
    for x, id0 in parts:
        for lo in range(0, x.shape[0], ORACLE_CHUNK):
            chunk = x[lo:lo + ORACLE_CHUNK]
            m = chunk.shape[0]
            ids = id0 + lo + np.arange(m)
            alive = None
            if dead:
                alive = jnp.asarray(~np.isin(ids, np.fromiter(dead, int)))
            d, i = search_bruteforce(chunk, queries, k=min(k, m),
                                     alive=alive)
            d = np.asarray(d).reshape(queries.shape[0], -1)
            i = np.asarray(i).reshape(queries.shape[0], -1)
            i = np.where(i >= 0, i + id0 + lo, -1)
            if best_d is None:
                best_d, best_i = d, i
                continue
            d_all = np.concatenate([best_d, d], axis=1)
            i_all = np.concatenate([best_i, i], axis=1)
            order = np.argsort(d_all, axis=1, kind="stable")[:, :k]
            best_d = np.take_along_axis(d_all, order, axis=1)
            best_i = np.take_along_axis(i_all, order, axis=1)
    return best_d, best_i


def true_distances(parts, queries, ids):
    """float64 z-normalized Euclidean distances of `ids` (host (Q, k))
    to the queries, gathered row by row from `parts` — an independent
    check that every reported distance belongs to its reported id."""
    import numpy as np

    rows = np.zeros(ids.shape + (L,), np.float64)
    for x, id0 in parts:
        sel = (ids >= id0) & (ids < id0 + x.shape[0])
        if sel.any():
            rows[sel] = np.asarray(x[ids[sel] - id0], np.float64)

    def zn(a):
        a = a - a.mean(-1, keepdims=True)
        return a / (a.std(-1, keepdims=True) + 1e-8)

    q = zn(np.asarray(queries, np.float64))
    return np.sqrt(((zn(rows) - q[:, None, :]) ** 2).sum(-1))


def check_knn(tag: str, d, i, parts, queries, want) -> int:
    """Assert (d, i) is an exact k-NN answer: distances match the
    oracle's, every id is at its reported distance, and ids differ from
    the oracle's only between candidates tied within TIE_D2.  Returns
    the number of such tie swaps."""
    import numpy as np

    d_or, i_or = want
    Q = queries.shape[0]
    d = np.asarray(d, np.float64).reshape(Q, -1)
    i = np.asarray(i).reshape(Q, -1)
    assert d.shape == d_or.shape, (tag, d.shape, d_or.shape)
    np.testing.assert_allclose(d, d_or, rtol=1e-5, atol=1e-5,
                               err_msg=f"{tag}: distances vs the oracle")
    found = i >= 0
    assert found.all(), f"{tag}: {int((~found).sum())} empty result slots"
    np.testing.assert_allclose(true_distances(parts, queries, i), d,
                               rtol=1e-4, atol=1e-4,
                               err_msg=f"{tag}: reported distance is not "
                                       f"the distance of the reported id")
    swaps = 0
    d2, d2_or = d ** 2, np.asarray(d_or, np.float64) ** 2
    for r in range(Q):
        if np.array_equal(i[r], i_or[r]):
            continue
        kth = d2_or[r, -1]
        for sid in set(i[r].tolist()) ^ set(i_or[r].tolist()):
            dd = (d2[r][i[r] == sid] if sid in i[r]
                  else d2_or[r][i_or[r] == sid])[0]
            assert abs(dd - kth) <= TIE_D2, (
                f"{tag}: query {r} id {sid} at d^2={dd} differs from the "
                f"oracle, k-th d^2={kth}")
        for j in np.nonzero(i[r] != i_or[r])[0]:
            assert abs(d2[r, j] - d2_or[r, j]) <= TIE_D2, (
                f"{tag}: query {r} slot {j} out of order")
        swaps += 1
    log(f"  {tag}: exact vs oracle ({swaps} queries with tied swaps)")
    return swaps


# --------------------------------------------------------------------- #
# one chip
# --------------------------------------------------------------------- #
def run_one_chip(seed: int, n_series: int, clock: CompileClock, dev):
    import jax
    import numpy as np

    from repro.api import FreshIndex, IndexConfig
    from repro.serve import EngineConfig

    with Phase("data", clock, [dev]):
        raw = walks(seed, 0, n_series)
        queries = walks(seed, 1, N_QUERIES)
        raw.block_until_ready()
    parts = [(raw, 0)]

    with Phase("build", clock, [dev]):
        index = FreshIndex.build(raw, IndexConfig(backend="pallas"))
        index.index.series.block_until_ready()
    assert index.n_series == n_series
    log(f"  index: {index.n_series} series, {index.index.n_leaves} leaves "
        f"of {index.index.leaf_capacity}")

    with Phase("oracle", clock, [dev]):
        want = {k: oracle(parts, queries, k) for k in KS}

    results = {}
    for k in KS:
        for backend in ("pallas", "ref"):
            with Phase(f"search k={k} {backend} (first call)", clock, [dev]):
                d, i = index.search(queries, k=k, backend=backend)
                d.block_until_ready()
            with Phase(f"search k={k} {backend}", clock, [dev]):
                d, i = index.search(queries, k=k, backend=backend)
                d.block_until_ready()
            check_knn(f"search k={k} {backend}", d, i, parts, queries,
                      want[k])
            results[(k, backend)] = (np.asarray(d), np.asarray(i))
        same = all(np.array_equal(a, b) for a, b in
                   zip(results[(k, "pallas")], results[(k, "ref")]))
        log(f"  k={k}: pallas and ref results bit-identical: {same}")

    k = 10
    d_fac, i_fac = results[(k, "pallas")]
    q_host = np.asarray(queries)
    with index.engine(EngineConfig(max_batch=16, workers=1,
                                   warm_ks=(k,))) as engine:
        with Phase("serve warmup", clock, [dev]):
            engine.warmup()
        misses = engine.stats()["plan_cache"]["misses"]
        with Phase(f"serve {N_SUBMITS} submits", clock, [dev]):
            futs = [engine.submit(q_host[s % N_QUERIES], k=k)
                    for s in range(N_SUBMITS)]
            got = [f.result(timeout=600) for f in futs]
        for s, (d, i) in enumerate(got):
            r = s % N_QUERIES
            d, i = np.asarray(d).reshape(-1), np.asarray(i).reshape(-1)
            assert np.array_equal(d, d_fac[r]) and np.array_equal(
                i, i_fac[r]), (
                f"submit {s}: served result differs from index.search "
                f"(ids equal: {np.array_equal(i, i_fac[r])}, max |dd| "
                f"{np.abs(d - d_fac[r]).max()})")
        st = engine.stats()
        assert st["plan_cache"]["misses"] == misses, (
            "the plan cache compiled after warmup", st["plan_cache"])
        texts = [p.as_text() for p in engine.plans.compiled()]
        assert texts and all("tpu_custom_call" in t for t in texts), (
            "a served plan holds no Mosaic kernel")
        log(f"  served {N_SUBMITS} submits bit-identical to index.search; "
            f"{len(texts)} plans, all with tpu_custom_call; "
            f"plan_cache={st['plan_cache']} "
            f"latency_ms={st['latency_ms']}")

    added = walks(seed, 2, N_ADD)
    # the first rows of the batch sit next to the queries, so the added
    # series and the deletions below change the answers
    added = added.at[:N_QUERIES].set(queries + 0.01 * walks(seed, 3,
                                                           N_QUERIES))
    parts.append((added, n_series))
    with Phase("add", clock, [dev]):
        index.add(added)
        d, i = index.search(queries, k=k)
        d.block_until_ready()
    check_knn("after add", d, i, parts, queries, oracle(parts, queries, k))
    assert (np.asarray(i)[:, 0] >= n_series).all(), "added rows not found"

    rng = np.random.default_rng(seed)
    top = np.asarray(results[(k, "pallas")][1])[:, 0]
    dead = set(top.tolist())                             # core top-1s
    dead |= set((n_series + np.arange(0, N_QUERIES, 2)).tolist())
    while len(dead) < N_DELETE:
        dead.add(int(rng.integers(0, n_series)))
    with Phase("delete", clock, [dev]):
        assert index.delete(sorted(dead)) == N_DELETE
        d, i = index.search(queries, k=k)
        d.block_until_ready()
    check_knn("after delete", d, i, parts, queries,
              oracle(parts, queries, k, dead))

    with Phase("compact", clock, [dev]):
        index.compact()
        index.index.series.block_until_ready()
    assert index.n_series == n_series + N_ADD - N_DELETE
    with Phase("search after compact", clock, [dev]):
        d, i = index.search(queries, k=k)
        d.block_until_ready()
    check_knn("after compact", d, i, parts, queries,
              oracle(parts, queries, k, dead))


# --------------------------------------------------------------------- #
# four chips
# --------------------------------------------------------------------- #
def run_sharded(seed: int, n_per_chip: int, clock: CompileClock, devs):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.api import FreshIndex, IndexConfig
    from repro.serve import EngineConfig

    n = n_per_chip * len(devs)
    dev0 = devs[0]
    cpu = jax.devices("cpu")[0]
    k = 10

    with Phase("data", clock, devs):
        chunks = [np.asarray(walks(seed, 10 + c, n_per_chip))
                  for c in range(len(devs))]
        host = np.concatenate(chunks)
        del chunks
        queries = walks(seed, 1, N_QUERIES)
    parts = [(host, 0)]

    # the global key sort needs every series in one place: the host.  The
    # CPU build runs the jnp summarization (the Pallas kernels compile
    # for the TPU only); search and serving below run backend="pallas".
    with Phase("build on host", clock, devs):
        with jax.default_device(cpu):
            index = FreshIndex.build(host, IndexConfig(backend="ref"))
            index.index.series.block_until_ready()
    with Phase("shard", clock, devs):
        with jax.default_device(cpu):
            index.shard(Mesh(np.array(devs), ("data",)))
        index.index.series.block_until_ready()
    for name in ("series", "leaf_lo"):
        arr = getattr(index.index, name)
        on = {s.device: s.data.shape[0] for s in arr.addressable_shards}
        assert set(on) == set(devs), (name, on)
        assert len(set(on.values())) == 1, (name, on)
        assert sum(on.values()) == arr.shape[0], (name, on)
        log(f"  {name}: {arr.shape} as {len(on)} shards of "
            f"{next(iter(on.values()))} rows")

    with Phase("oracle (device 0)", clock, devs):
        want = oracle(parts, queries, k)     # host chunks -> device 0
    with Phase(f"sharded search k={k} (first call)", clock, devs):
        d, i = index.search(queries, k=k, backend="pallas")
        d.block_until_ready()
    with Phase(f"sharded search k={k}", clock, devs):
        d, i = index.search(queries, k=k, backend="pallas")
        d.block_until_ready()
    check_knn("sharded search", d, i, parts, queries, want)
    d_fac, i_fac = np.asarray(d), np.asarray(i)

    q_host = np.asarray(queries)
    with index.engine(EngineConfig(max_batch=4, workers=1, warm_ks=(k,),
                                   backend="pallas")) as engine:
        with Phase("sharded serve warmup", clock, devs):
            engine.warmup()
        misses = engine.stats()["plan_cache"]["misses"]
        with Phase(f"sharded serve {N_QUERIES} submits", clock, devs):
            futs = [engine.submit(q_host[s], k=k) for s in range(N_QUERIES)]
            got = [f.result(timeout=600) for f in futs]
        for s, (d, i) in enumerate(got):
            d, i = np.asarray(d).reshape(-1), np.asarray(i).reshape(-1)
            assert np.array_equal(d, d_fac[s]) and np.array_equal(
                i, i_fac[s]), (
                f"submit {s}: served result differs from index.search "
                f"(ids equal: {np.array_equal(i, i_fac[s])}, max |dd| "
                f"{np.abs(d - d_fac[s]).max()})")
        assert engine.stats()["plan_cache"]["misses"] == misses
        texts = [p.as_text() for p in engine.plans.compiled()]
        assert texts and all("tpu_custom_call" in t for t in texts)
        log(f"  served {N_QUERIES} sharded submits bit-identical to "
            f"index.search; {len(texts)} plans with tpu_custom_call")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the main path on one chip; 4: only the "
                         "sharded phase, over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    platforms = os.environ.get("JAX_PLATFORMS")
    if args.chips == 4 and platforms and "cpu" not in platforms.split(","):
        # the sharded phase builds on the host: keep the CPU backend up
        # beside the accelerator (the accelerator stays the default)
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devs[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devs)}", file=sys.stderr)
        return 1
    devs = devs[:args.chips]

    from repro.launch.compile_cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")
    log(f"device: platform={devs[0].platform} "
        f"device_kind={devs[0].device_kind} count={len(devs)}")
    clock = CompileClock()
    if args.chips == 1:
        run_one_chip(args.seed, 1 << 22, clock, devs[0])
    else:
        run_sharded(args.seed, 1 << 22, clock, devs)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
