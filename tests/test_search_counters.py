"""The search plan's own counters (rounds, live query-rounds, refined
(query, leaf) pairs, kernel rows, shard live rounds) and the facade's
record of them (`repro.obs`).  The sharded plan's are checked on four
devices in tests/test_mesh_build.py.

The counts are checked against the same loop re-run on the host: the
plan's lower bounds and priority queue, then one refine round at a time
over all the batch's rows, each round's pruning decided in numpy.  That
loop's answers are also the plan's, bit for bit: neither the counters
nor the plan's phases of halving width change an answer."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.api import FreshIndex, IndexConfig
from repro.data.synthetic import query_workload, random_walk
from repro.serve import EngineConfig

#: the module (`repro.core.search` names a function there)
S = importlib.import_module("repro.core.search")

L = 64
K = 4                    # leaves per round
M = 16                   # leaf capacity


@pytest.fixture(scope="module")
def small():
    walks = random_walk(1024, L, seed=41)
    queries = query_workload(walks, 6, noise_sigma=0.1, seed=42)
    extra = random_walk(40, L, seed=43)
    return walks, jnp.asarray(queries), extra


def host_loop(idx, queries, *, k, backend, stop_eps=0.0, stop_leaves=None,
              trail=None):
    """The plan's refinement loop with its pruning and termination
    decided on the host: ((rounds, live, refined), dist, ids).  Every
    round refines all Q rows; `trail`, where given, gets the number of
    live queries at the start of each round."""
    inv, budget = S._stop_knobs(stop_eps, stop_leaves, None)
    q, q_paa, q_sq = S.prepare_query_rows(queries, True, index=idx)
    lb = S.leaf_lower_bounds(idx, q_paa, L, backend)
    cap = S._rounds_cap(idx.n_leaves, K, None, budget)
    order, sorted_lb = (np.asarray(a) for a in
                        S._pq_order(lb, K, cap, budget))
    Q = queries.shape[0]
    bsf_d = jnp.full((Q, k), S.BIG)
    bsf_e = jnp.zeros((Q, k), jnp.int32)
    rounds = live = refined = 0
    for cursor in range(0, cap * K, K):
        kth = np.asarray(bsf_d)[:, -1]
        bound = kth * np.float32(inv) if stop_eps else kth
        if not (sorted_lb[:, cursor] < bound).any():
            break
        alive = sorted_lb[:, cursor:cursor + K] < bound[:, None]
        rounds += 1
        if trail is not None:
            trail.append(int(alive[:, 0].sum()))
        live += int(alive[:, 0].sum())
        refined += int(alive.sum())
        bsf_d, bsf_e = S._refine_round(
            q, q_sq, idx.series, idx.sq_norms,
            jnp.asarray(order[:, cursor:cursor + K]), jnp.asarray(alive),
            bsf_d, bsf_e, M=M, k=k, backend=backend)
    found = bsf_d < S.BIG
    ids = jnp.where(found, idx.perm[bsf_e], -1)
    d = jnp.where(found, S.direct_sq(q, idx.series, bsf_e), bsf_d)
    resort = jnp.argsort(d, axis=1)
    d = jnp.sqrt(jnp.take_along_axis(d, resort, axis=1))
    ids = jnp.take_along_axis(ids, resort, axis=1)
    return (rounds, live, refined), np.asarray(d), np.asarray(ids)


RULES = {"exact": {}, "eps": {"stop_eps": 0.5}, "leaves": {"stop_leaves": 9}}


@pytest.mark.parametrize("delta", [False, True], ids=["core", "delta"])
@pytest.mark.parametrize("rule", list(RULES))
@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_plan_counts_match_the_host_loop(small, backend, rule, delta):
    walks, queries, extra = small
    ix = FreshIndex.build(walks, IndexConfig(leaf_capacity=M,
                                             round_leaves=K,
                                             backend=backend))
    kw = dict(k=5, round_leaves=K, backend=backend, **RULES[rule])
    want, d_host, i_host = host_loop(ix.index, queries, k=5,
                                     backend=backend, **RULES[rule])
    if delta:
        d, i, c = S.snapshot_search(ix.index, jnp.asarray(extra), queries,
                                    n_base=walks.shape[0], **kw)
    else:
        d, i, c = S.search_plan(ix.index, queries, **kw)
        # the counters change no answer
        np.testing.assert_array_equal(np.asarray(d), d_host)
        np.testing.assert_array_equal(np.asarray(i), i_host)
    assert c.dtype == jnp.int32 and c.shape == (len(S.COUNTERS),)
    got = tuple(int(v) for v in c)
    assert got[:3] == want
    rounds, live, refined = want
    assert 0 < live <= rounds * queries.shape[0]
    assert live <= refined <= live * K
    # six rows: one phase, every row in every round's refine call
    assert S.phase_widths(queries.shape[0]) == (queries.shape[0],)
    assert got[3] == queries.shape[0] * rounds
    # one device refines in every round it runs
    assert got[4] == rounds

    # the facade records the same counts, with a pending delta too
    if delta:
        ix.add(extra)
    mode = {} if rule == "exact" else {
        "mode": "approx", "stop_eps": RULES[rule].get("stop_eps"),
        "max_leaves": RULES[rule].get("stop_leaves")}
    ix.search(queries, k=5, **mode)
    rec = obs.records(last=1)[0]
    assert (rec.queries, rec.round_leaves) == (queries.shape[0], K)
    assert obs.counts(rec) == want


def test_search_adds_no_device_to_host_transfer(small, monkeypatch):
    """No copy to the host on the search path.  The transfer guard holds
    on an accelerator; on the CPU it lets every copy pass, so each read
    of an array's host value (`int()`, `bool()`, `tolist()`,
    `__array__`) is counted as well."""
    from jax._src.array import ArrayImpl
    walks, queries, _ = small
    ix = FreshIndex.build(walks, IndexConfig(leaf_capacity=M,
                                             round_leaves=K))
    ix.search(queries, k=3)                      # compiles outside
    value = ArrayImpl._value
    copied = []
    monkeypatch.setattr(ArrayImpl, "_value", property(
        lambda self: copied.append(self.shape) or value.fget(self)))
    with jax.transfer_guard_device_to_host("disallow"):
        d, i = ix.search(queries, k=3)
    assert copied == []
    rec = obs.records(last=1)[0]
    assert obs.counts(rec)[0] > 0                # read: copied
    assert copied == [(len(S.COUNTERS),)]
    assert np.asarray(i).shape == (queries.shape[0], 3)


def test_ring_stays_bounded():
    obs.clear()
    n = obs.CAPACITY + 5
    for j in range(n):
        obs.record(j, K, np.array([j, 0, 0], np.int32))
    got = obs.records()
    assert len(got) == obs.CAPACITY
    assert got[0].queries == 5 and got[-1].queries == n - 1
    assert [r.queries for r in obs.records(last=3)] == [n - 3, n - 2, n - 1]
    assert len(obs.records(last=10 * n)) == obs.CAPACITY
    tot = obs.totals()
    assert tot["searches"] == obs.CAPACITY
    assert tot["rounds"] == sum(range(5, n))
    obs.record(2, K, None)                       # a call not counted
    tot = obs.totals()
    assert tot["uncounted"] == 1 and tot["rounds"] == sum(range(6, n))
    obs.clear()
    assert obs.records() == [] and obs.totals()["searches"] == 0


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_engine_sums_match_the_facade(small, backend):
    walks, queries, _ = small
    ix = FreshIndex.build(walks, IndexConfig(leaf_capacity=M,
                                             round_leaves=K,
                                             backend=backend))
    obs.clear()
    ix.search(queries[:4], k=5)
    ix.search(queries[2:6], k=5)
    facade = obs.totals()
    recs = obs.records()
    with ix.engine(EngineConfig(max_batch=4)) as eng:
        # one submit per bucket of 4: no pad rows, the facade's programs
        eng.submit(np.asarray(queries[:4]), k=5).result(timeout=120)
        eng.submit(np.asarray(queries[2:6]), k=5).result(timeout=120)
        st = eng.stats()
    assert st["live_query_rounds"] == facade["live_query_rounds"]
    assert st["refined_pairs"] == facade["refined_pairs"]
    assert st["counted_rows"] == facade["queries"] == 8
    assert st["rounds_sum"] == sum(obs.counts(r)[0] * r.queries
                                   for r in recs)
    visited = st["quality"]["tiers"]["exact"]["visited_leaves_per_query"]
    assert visited == pytest.approx(facade["refined_pairs"] / 8)


@pytest.fixture(scope="module")
def wide():
    """64 queries whose rounds spread widely: near copies of indexed
    series finish in a few rounds, noisier ones run on, so the number
    of live queries falls through every phase width of a 64-row plan."""
    walks = random_walk(2048, L, seed=51)
    queries = np.concatenate([
        query_workload(walks, 16, noise_sigma=sigma, seed=52 + j)
        for j, sigma in enumerate((0.05, 0.2, 0.5, 1.0))])
    ix = FreshIndex.build(walks, IndexConfig(leaf_capacity=M,
                                             round_leaves=K))
    return walks, jnp.asarray(queries), ix.index


def phase_schedule(trail, Q):
    """The rows each round of a phased plan refines, from the live
    queries at the start of each round: a phase of B rows runs while
    more than the next phase's rows are live."""
    widths = [Q]
    while widths[-1] // 2 >= 8:
        widths.append(widths[-1] // 2)
    p, rows = 0, []
    for n in trail:
        while p + 1 < len(widths) and n <= widths[p + 1]:
            p += 1
        rows.append(widths[p])
    return widths, rows


@pytest.mark.parametrize("k", [1, 10])
@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_phases_keep_every_answer_and_count(wide, backend, k):
    """A 64-row batch runs phases of 64, 32, 16 and 8 rows; each query's
    answer is the one it gets alone, bit for bit, and the oracle's; the
    plan's rounds and pruning are the host loop's, which refines all 64
    rows every round; only the refine call's rows shrink."""
    walks, queries, idx = wide
    Q = queries.shape[0]
    kw = dict(k=k, round_leaves=K, backend=backend)
    d, i, c = S.search_plan(idx, queries, **kw)
    d, i = np.asarray(d), np.asarray(i)
    rounds, live, refined, kernel_rows, shard_live = (int(v) for v in c)
    assert shard_live == rounds

    trail = []
    want, d_host, i_host = host_loop(idx, queries, k=k, backend=backend,
                                     trail=trail)
    assert (rounds, live, refined) == want
    np.testing.assert_array_equal(d, d_host)
    np.testing.assert_array_equal(i, i_host)
    widths, rows = phase_schedule(trail, Q)
    assert S.phase_widths(Q) == tuple(widths) == (64, 32, 16, 8)
    assert set(rows) == set(widths)         # every phase ran a round
    assert kernel_rows == sum(rows)
    assert live <= kernel_rows < Q * rounds

    for j in range(Q):                      # alone: one phase
        dj, ij, _ = S.search_plan(idx, queries[j:j + 1], **kw)
        np.testing.assert_array_equal(np.asarray(dj)[0], d[j])
        np.testing.assert_array_equal(np.asarray(ij)[0], i[j])
    db, ib = S.search_bruteforce(jnp.asarray(walks), queries, k=k)
    db, ib = np.asarray(db).reshape(Q, k), np.asarray(ib).reshape(Q, k)
    np.testing.assert_array_equal(i, ib)
    np.testing.assert_allclose(d, db, rtol=1e-5, atol=1e-5)

    # the facade records the five counts; `obs.counts` reads three
    obs.clear()
    ix = FreshIndex.build(walks, IndexConfig(leaf_capacity=M,
                                             round_leaves=K,
                                             backend=backend))
    ix.search(queries, k=k)
    rec = obs.records(last=1)[0]
    assert obs.counts(rec) == want
    assert obs.kernel_rows(rec) == kernel_rows
    assert (obs.shard_live_rounds(rec), rec.shards) == (rounds, 1)
    obs.clear()
