"""A build over series row-sharded over a mesh, and the sharded plan's
counters and phases, on four CPU devices.

`FreshIndex.build` on an array whose rows are split over the mesh runs
one program in which each device builds its own block; the index comes
back placed over the mesh, and the sharded plan searches it.  The
pytest process keeps one device, so everything runs once, in one
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=4,
which prints what the tests read as its last line."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from plan_phases import phase_schedule

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHARDS = 4
K = 4                    # leaves per round
Q = 8                    # queries: one phase
WIDE = 64                # queries of the phased batch

SCRIPT = textwrap.dedent("""
    import json, os, sys, tempfile
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro import obs
    from repro.api import FreshIndex, IndexConfig
    from repro.core.index import FlatIndex, build_index
    from repro.core.search import search_bruteforce
    from repro.data.synthetic import query_workload, random_walk
    from repro.serve import EngineConfig

    import importlib
    SP = importlib.import_module("repro.core.search")

    S, K, Q, L, M = 4, 4, 8, 64, 16
    mesh = Mesh(np.array(jax.devices()[:S]), ("data",))
    rows_of = NamedSharding(mesh, P("data"))
    out = {}

    def host_loop(idx, qs, k, backend):
        # the sharded plan's rounds (sync_every 1) with every decision
        # taken on the host, shard by shard, all rows every round: the
        # counters (rounds, live, refined, shard live rounds) and the
        # live queries at the start of each round
        Q = qs.shape[0]
        parts = []
        for s in range(S):
            part = FlatIndex(*[jnp.asarray(np.asarray(a)[
                s * (a.shape[0] // S):(s + 1) * (a.shape[0] // S)])
                for a in idx])
            q, q_paa, q_sq = SP.prepare_query_rows(qs, True, index=part)
            lb = SP.leaf_lower_bounds(part, q_paa, L, backend)
            cap = SP._rounds_cap(part.n_leaves, K, None, None)
            order, slb = SP._pq_order(lb, K, cap, None)
            parts.append([part, q, q_sq, np.asarray(order), np.asarray(slb),
                          jnp.full((Q, k), SP.BIG),
                          jnp.zeros((Q, k), jnp.int32)])
        pb = np.full(Q, np.float32(SP.BIG), np.float32)

        def bound(p):
            return np.minimum(pb, np.asarray(p[5])[:, -1])

        def live_anywhere(cursor):
            return np.any([p[4][:, cursor] < bound(p) for p in parts], 0)

        rounds = live = refined = worked = cursor = 0
        trail = []
        while cursor < cap * K and live_anywhere(cursor).any():
            rounds += 1
            trail.append(int(live_anywhere(cursor).sum()))
            live += trail[-1]
            for p in parts:
                alive = p[4][:, cursor:cursor + K] < bound(p)[:, None]
                refined += int(alive.sum())
                worked += int(alive.any())
                p[5], p[6] = SP._refine_round(
                    p[1], p[2], p[0].series, p[0].sq_norms,
                    jnp.asarray(p[3][:, cursor:cursor + K]),
                    jnp.asarray(alive), p[5], p[6], M=M, k=k,
                    backend=backend)
            pb = np.minimum(pb, np.min([np.asarray(p[5])[:, -1]
                                        for p in parts], 0))
            cursor += K
        return [rounds, live, refined, worked], trail

    def blocks(arr):
        # (first row, device) of each device's block, in row order
        return sorted([sh.index[0].start or 0, sh.device.id]
                      for sh in arr.addressable_shards)

    # 256 rows a shard fill 16 leaves; 200 pad each shard's last leaf
    for backend, rows in (("ref", 256), ("pallas", 200)):
        r = out[backend] = {}
        walks = random_walk(S * rows, L, seed=61)
        qs = jnp.asarray(query_workload(walks, Q, noise_sigma=0.3, seed=62))
        cfg = IndexConfig(leaf_capacity=M, round_leaves=K, backend=backend)
        x = jax.device_put(jnp.asarray(walks), rows_of)
        ix = FreshIndex.build(x, cfg)
        r["mesh"] = ix.mesh == mesh and ix.mesh_axis == "data"

        # each device's part is build_index over its block alone, with
        # the ids offset by the block's first row
        got = {f: np.asarray(getattr(ix.index, f)) for f in FlatIndex._fields}
        same = {}
        for s in range(S):
            want = build_index(jnp.asarray(walks[s * rows:(s + 1) * rows]),
                               leaf_capacity=M, backend=backend)
            for f in FlatIndex._fields:
                w = np.asarray(getattr(want, f))
                if f == "perm":
                    w = np.where(w >= 0, w + s * rows, -1)
                part = got[f].shape[0] // S
                same.setdefault(f, True)
                same[f] &= bool(np.array_equal(
                    got[f][s * part:(s + 1) * part], w))
        r["same_as_blocks"] = same
        part = ix.index.series.shape[0] // S
        r["placement"] = {f: blocks(getattr(ix.index, f)) for f in
                          ("series", "perm", "leaf_lo", "leaf_valid")}
        r["part_rows"] = part

        # shard() on the mesh it was built on moves nothing
        before = ix.index
        ix.shard(mesh)
        r["shard_noop"] = (ix.index is before
                           and all(getattr(ix.index, f) is getattr(before, f)
                                   for f in FlatIndex._fields))

        obs.clear()
        r["knn"] = {}
        for k in (1, 10):
            d, i = ix.search(qs, k=k)
            db, ib = search_bruteforce(jnp.asarray(walks), qs, k=k)
            r["knn"][k] = [bool(np.array_equal(np.asarray(i),
                                               np.asarray(ib))),
                           float(np.abs(np.asarray(d)
                                        - np.asarray(db)).max())]
        recs = obs.records()
        r["host_counts"], r["host_trail"] = host_loop(ix.index, qs, 10,
                                                      backend)
        r["records"] = [[rec.queries, rec.round_leaves, rec.shards,
                         [int(v) for v in rec.counts.tolist()],
                         list(obs.counts(rec)), obs.kernel_rows(rec),
                         obs.shard_live_rounds(rec)] for rec in recs]

        # the engine reads the sharded counts through its counted path
        with ix.engine(EngineConfig(max_batch=Q)) as eng:
            d_e, i_e = eng.submit(np.asarray(qs), k=10).result(timeout=600)
            st = eng.stats()
        last = recs[-1].counts.tolist()
        i_f = np.asarray(ix.search(qs, k=10)[1])
        r["engine"] = [bool(np.array_equal(np.asarray(i_e), i_f)),
                       st["live_query_rounds"] == last[1],
                       st["refined_pairs"] == last[2],
                       st["counted_rows"] == Q,
                       st["rounds_sum"] == last[0] * Q]

        # add and delete on a mesh-built index stay exact
        extra = random_walk(24, L, seed=63)
        ix.add(extra)
        both = jnp.asarray(np.concatenate([walks, extra]))
        ix.delete([3, S * rows - 1, S * rows + 5])
        alive = np.ones(both.shape[0], bool)
        alive[[3, S * rows - 1, S * rows + 5]] = False
        d, i = ix.search(qs, k=10)
        db, ib = search_bruteforce(both, qs, k=10, alive=jnp.asarray(alive))
        r["add_delete"] = bool(np.array_equal(np.asarray(i), np.asarray(ib)))

    # 64 queries whose rounds spread widely: near copies of indexed
    # series finish in a few rounds, noisier ones run on, so the live
    # queries fall through every phase width of a 64-row plan
    walks = random_walk(S * 2048, L, seed=51)
    wide = jnp.asarray(np.concatenate([
        query_workload(walks, 16, noise_sigma=sigma, seed=52 + j)
        for j, sigma in enumerate((0.05, 0.2, 0.5, 1.0))]))
    for backend in ("ref", "pallas"):
        r = out[backend]["wide"] = {}
        ix = FreshIndex.build(jax.device_put(jnp.asarray(walks), rows_of),
                              IndexConfig(leaf_capacity=M, round_leaves=K,
                                          backend=backend))
        for k in (1, 10):
            obs.clear()
            d, i = (np.asarray(a).reshape(len(wide), k)
                    for a in ix.search(wide, k=k))
            rec = obs.records(last=1)[0]
            alone = [[np.asarray(a).reshape(k) for a in
                      ix.search(wide[j:j + 1], k=k)]
                     for j in range(len(wide))]
            db, ib = (np.asarray(a).reshape(len(wide), k) for a in
                      search_bruteforce(jnp.asarray(walks), wide, k=k))
            host, trail = host_loop(ix.index, wide, k, backend)
            r[k] = {"counts": [int(v) for v in rec.counts.tolist()],
                    "kernel_rows": obs.kernel_rows(rec),
                    "host_counts": host, "host_trail": trail,
                    "alone": all(np.array_equal(dj, d[j])
                                 and np.array_equal(ij, i[j])
                                 for j, (dj, ij) in enumerate(alone)),
                    "oracle": [bool(np.array_equal(i, ib)),
                               float(np.abs(d - db).max())]}
        obs.clear()

    # the spans around build and shard
    walks = random_walk(S * 64, L, seed=64)
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            ix = FreshIndex.build(jax.device_put(jnp.asarray(walks), rows_of),
                                  IndexConfig(leaf_capacity=M))
            ix.index.series.block_until_ready()
            FreshIndex.build(walks, IndexConfig(leaf_capacity=M)).shard(mesh)
        from jax.profiler import ProfileData
        import glob
        path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                         recursive=True)[0]
        names = [e.name for p in ProfileData.from_file(path).planes
                 for ln in p.lines for e in ln.events]
    out["spans"] = {n: names.count(n) for n in (obs.BUILD_SPAN,
                                                obs.SHARD_SPAN)}
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={SHARDS}",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}"
    return json.loads(p.stdout.strip().splitlines()[-1])


BACKENDS = ["ref", "pallas"]


@pytest.mark.parametrize("backend", BACKENDS)
def test_each_device_builds_its_own_block(runs, backend):
    r = runs[backend]
    assert r["mesh"] is True
    assert r["same_as_blocks"] == {f: True for f in r["same_as_blocks"]}
    # every array split by rows, block s on device s
    part = r["part_rows"]
    assert r["placement"]["series"] == [[s * part, s] for s in range(SHARDS)]
    assert r["placement"]["perm"] == r["placement"]["series"]
    leaves = part // 16
    for f in ("leaf_lo", "leaf_valid"):
        assert r["placement"][f] == [[s * leaves, s] for s in range(SHARDS)]


@pytest.mark.parametrize("backend", BACKENDS)
def test_shard_on_the_build_mesh_moves_nothing(runs, backend):
    assert runs[backend]["shard_noop"] is True


@pytest.mark.parametrize("backend", BACKENDS)
def test_sharded_search_matches_the_oracle_with_global_ids(runs, backend):
    for k, (ids_equal, dist_err) in runs[backend]["knn"].items():
        assert ids_equal, k
        assert dist_err <= 1e-5, k
    assert runs[backend]["add_delete"] is True


@pytest.mark.parametrize("backend", BACKENDS)
def test_sharded_counters_keep_their_invariants(runs, backend):
    recs = runs[backend]["records"]
    assert len(recs) == 2
    for queries, round_leaves, shards, c, first3, kernel_rows, live in recs:
        assert (queries, round_leaves, shards) == (Q, K, SHARDS)
        rounds, live_q, refined, rows, shard_live = c
        assert first3 == [rounds, live_q, refined]
        assert (kernel_rows, live) == (rows, shard_live)
        assert rounds > 0
        # every round some query is live on some shard, and that shard
        # refines its first leaf
        assert rounds <= live_q <= Q * rounds
        assert live_q <= refined <= rows * K
        # every device refines every live row of each round, and at
        # most the whole batch (a batch of Q < 16 rows runs one phase:
        # the schedule's rows, checked against the host loop below)
        assert SHARDS * live_q <= rows <= SHARDS * Q * rounds
        assert rounds <= shard_live <= SHARDS * rounds


@pytest.mark.parametrize("backend", BACKENDS)
def test_sharded_counters_match_the_host_loop(runs, backend):
    """The k = 10 search's counters are those of the same rounds taken
    with every pruning and stopping decision made on the host."""
    r = runs[backend]
    rounds, live, refined, worked = r["host_counts"]
    widths, rows = phase_schedule(r["host_trail"], Q)
    assert widths == [Q]
    assert r["records"][-1][3] == [rounds, live, refined,
                                   SHARDS * sum(rows), worked]


@pytest.mark.parametrize("k", [1, 10])
@pytest.mark.parametrize("backend", BACKENDS)
def test_sharded_phases_keep_every_answer_and_count(runs, backend, k):
    """A 64-row batch on the mesh runs phases of 64, 32, 16 and 8 rows
    on every device; each query's answer is the one it gets alone, bit
    for bit, and the oracle's with global ids; the plan's rounds,
    liveness and pruning are the host loop's, which refines all 64 rows
    every round; only the refine calls' rows shrink."""
    w = runs[backend]["wide"][str(k)]
    rounds, live, refined, rows, shard_live = w["counts"]
    assert [rounds, live, refined, shard_live] == w["host_counts"]
    widths, sched = phase_schedule(w["host_trail"], WIDE)
    assert widths == [64, 32, 16, 8]
    assert set(sched) == set(widths)          # every phase ran a round
    assert rows == w["kernel_rows"] == SHARDS * sum(sched)
    assert SHARDS * live <= rows < SHARDS * WIDE * rounds
    assert w["alone"] is True
    ids_equal, dist_err = w["oracle"]
    assert ids_equal
    assert dist_err <= 1e-5


@pytest.mark.parametrize("backend", BACKENDS)
def test_engine_counts_the_sharded_plan(runs, backend):
    assert runs[backend]["engine"] == [True] * 5


def test_build_and_shard_spans(runs):
    assert runs["spans"] == {"fresh.build": 2, "fresh.shard": 1}
