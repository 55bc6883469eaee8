"""Quality subsystem (repro.quality): recall-tiered approximate search.

The acceptance criteria of the quality PR, machine-checked:

* approximate results carry TRUE distances — every returned (id, dist)
  pair matches the brute-force distance to that live series exactly;
* leaf-cap containment — with an explicit `max_leaves=m` rule the core
  result set is a subset of the top-m PQ leaf candidates (the delta
  scan stays exact and may contribute any pending row);
* calibrated recall — after `calibrate()`, `search(mode="approx",
  recall_target=0.95)` meets the target on the calibration holdout for
  k in {1, 5, 10} on both kernel backends;
* exact stays exact — `mode="exact"` is bit-identical to the
  tombstone-aware brute-force oracle, locally and on a mesh, and
  rejects stop knobs;
* `plan_key` covers every `Knobs` field, so a knob added to Knobs can
  never silently alias exact and approx in either cache;
* `update(sid, series)` is one atomic epoch publish under a stable id —
  a concurrent reader never observes zero or two live rows for it.
"""

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import FreshIndex, IndexConfig
from repro.core import search_bruteforce
from repro.data.synthetic import query_workload, random_walk
from repro.quality import (EXACT, StopRule, holdout_queries,
                           index_fingerprint, oracle_topk,
                           pq_leaf_candidates, recall_at_k)
from repro.serve import EngineConfig, Knobs, plan_key

L = 64
N_CORE = 256
N_DELTA = 32
TARGET = 0.95


@pytest.fixture(scope="module")
def data():
    walks = random_walk(N_CORE, L, seed=41)
    extra = random_walk(N_DELTA, L, seed=42)
    queries = query_workload(np.concatenate([walks, extra]), 8,
                             noise_sigma=0.05, seed=43)
    return walks, extra, queries


def _make_index(data) -> FreshIndex:
    """256 core rows (32 leaves at capacity 8) + 32 delta rows."""
    walks, extra, _ = data
    ix = FreshIndex.build(walks, IndexConfig(leaf_capacity=8))
    ix.add(extra)
    return ix


@pytest.fixture(scope="module")
def calibrated(data):
    """One calibrated index + the exact holdout it was fitted on."""
    ix = _make_index(data)
    hq = holdout_queries(ix, n=24, noise=0.25, seed=5)
    table = ix.calibrate(ks=(1, 5, 10), targets=(TARGET,), queries=hq,
                         eps_grid=(0.0, 0.25, 0.5), leaves_grid=(8, 16),
                         repeat=1)
    return ix, hq, table


# --------------------------------------------------------------------- #
# true distances: approx may skip leaves, it may not invent numbers
# --------------------------------------------------------------------- #
def test_approx_distances_are_true_distances(data, calibrated):
    walks, extra, queries = data
    ix, _, _ = calibrated
    raw = np.concatenate([walks, extra]).astype(np.float32)
    q = jnp.asarray(queries)
    d, i = ix.search(q, k=10, mode="approx", recall_target=TARGET)
    d, i = np.asarray(d), np.asarray(i)
    # the full distance row per query, from the seed oracle
    d_all, i_all = search_bruteforce(jnp.asarray(raw), q, k=raw.shape[0],
                                     znorm=ix.config.znorm)
    d_all, i_all = np.asarray(d_all), np.asarray(i_all)
    for r in range(q.shape[0]):
        true = dict(zip(i_all[r].tolist(), d_all[r].tolist()))
        for col in range(10):
            sid = int(i[r, col])
            assert sid in true, f"approx returned unreal id {sid}"
            np.testing.assert_allclose(d[r, col], true[sid], rtol=1e-4,
                                       atol=1e-4)


# --------------------------------------------------------------------- #
# containment: an explicit leaf cap bounds the core candidate set
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("m", [4, 8])
def test_approx_results_within_leaf_candidates(data, m):
    _, _, queries = data
    ix = _make_index(data)
    q = jnp.asarray(queries)
    d, i = ix.search(q, k=10, mode="approx", max_leaves=m)
    cands = pq_leaf_candidates(ix, q, m)
    delta_ids = set(range(ix._delta_id0, ix._delta_id0 + N_DELTA))
    for r in range(q.shape[0]):
        allowed = set(cands[r].tolist()) | delta_ids
        got = set(np.asarray(i)[r].tolist()) - {-1}
        assert got <= allowed, (m, r, sorted(got - allowed))


# --------------------------------------------------------------------- #
# calibrated recall on the holdout, both backends
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("k", [1, 5, 10])
def test_calibrated_recall_meets_target(calibrated, backend, k):
    ix, hq, table = calibrated
    entry = table.lookup(k, TARGET)
    assert entry is not None
    d, i = ix.search(jnp.asarray(hq), k=k, mode="approx",
                     recall_target=TARGET, backend=backend)
    d_o, i_o = oracle_topk(ix, hq, k)
    rec = recall_at_k(np.asarray(i), i_o)
    assert rec >= TARGET, (backend, k, rec, entry.rule)
    # returned distances are sorted within a query and real (no sentinel
    # leakage); the facade squeezes k=1 results to (Q,)
    d = np.asarray(d)
    if d.ndim == 2:
        assert np.all(np.diff(d, axis=1) >= -1e-5)
    assert np.all(d < 1e15)


def test_calibration_persists_and_tracks_freshness(data, tmp_path):
    ix = _make_index(data)
    hq = holdout_queries(ix, n=8, seed=9)
    ix.calibrate(ks=(10,), targets=(TARGET,), queries=hq,
                 eps_grid=(0.0, 0.25), leaves_grid=(8,), repeat=1)
    assert ix.is_calibration_fresh()
    fp = index_fingerprint(ix)
    ix.save(str(tmp_path / "ckpt"))
    out = FreshIndex.load(str(tmp_path / "ckpt"))
    assert out.calibration is not None
    assert out.calibration.fingerprint == fp
    assert out.is_calibration_fresh()
    # a lookup on the loaded table resolves without re-fitting
    assert out.resolve_stop_rule("approx", k=10,
                                 recall_target=TARGET) is not None
    # mutation makes the table stale (but it still resolves)
    out.add(random_walk(1, L, seed=77))
    assert not out.is_calibration_fresh()
    out.resolve_stop_rule("approx", k=10, recall_target=TARGET)


def test_stop_rule_resolution_errors(data):
    ix = _make_index(data)
    with pytest.raises(ValueError, match="exact"):
        ix.resolve_stop_rule("exact", k=10, stop_eps=0.1)
    with pytest.raises(ValueError, match="calibrat"):
        ix.resolve_stop_rule("approx", k=10)       # no table fitted
    with pytest.raises(ValueError):
        ix.search(jnp.zeros((1, L)), k=10, mode="warp")
    assert ix.resolve_stop_rule("exact", k=10) is EXACT
    r = ix.resolve_stop_rule("approx", k=10, stop_eps=0.1, max_leaves=4)
    assert r == StopRule(eps=0.1, max_leaves=4)
    with pytest.raises(ValueError):
        StopRule(eps=-1.0)
    with pytest.raises(ValueError):
        StopRule(max_leaves=0)


# --------------------------------------------------------------------- #
# exact mode stays the seed oracle — tombstones, both backends, mesh
# --------------------------------------------------------------------- #
DELETED = [3, 17, 120, 256, 270]


@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("k", [1, 10])
def test_exact_mode_bit_identical_to_oracle(data, backend, k):
    walks, extra, queries = data
    ix = _make_index(data)
    assert ix.delete(DELETED) == len(DELETED)
    raw = np.concatenate([walks, extra]).astype(np.float32)
    alive = np.ones(raw.shape[0], bool)
    alive[DELETED] = False
    q = jnp.asarray(queries)
    d, i = ix.search(q, k=k, mode="exact", backend=backend)
    d_o, i_o = search_bruteforce(jnp.asarray(raw), q, k=k,
                                 znorm=ix.config.znorm,
                                 alive=jnp.asarray(alive))
    assert np.array_equal(np.asarray(d), np.asarray(d_o)), (backend, k)
    assert np.array_equal(np.asarray(i), np.asarray(i_o)), (backend, k)


def test_exact_mode_bit_identical_on_mesh(data):
    walks, extra, queries = data
    ix = _make_index(data)
    ix.delete(DELETED)
    mesh = jax.make_mesh((1,), ("data",))
    ix.shard(mesh)
    raw = np.concatenate([walks, extra]).astype(np.float32)
    alive = np.ones(raw.shape[0], bool)
    alive[DELETED] = False
    q = jnp.asarray(queries)
    d, i = ix.search(q, k=10, mode="exact")
    d_o, i_o = search_bruteforce(jnp.asarray(raw), q, k=10,
                                 znorm=ix.config.znorm,
                                 alive=jnp.asarray(alive))
    assert np.array_equal(np.asarray(d), np.asarray(d_o))
    assert np.array_equal(np.asarray(i), np.asarray(i_o))
    # and the sharded approx path still answers with true live ids
    da, ia = ix.search(q, k=10, mode="approx", max_leaves=8)
    assert not (set(np.asarray(ia).ravel().tolist()) & set(DELETED))


# --------------------------------------------------------------------- #
# plan_key reflection: every Knobs field keys both caches
# --------------------------------------------------------------------- #
def test_plan_key_tracks_every_knob_field():
    key = plan_key(7, Knobs())
    assert key[0] == 7
    assert len(key) == 1 + len(dataclasses.fields(Knobs)), (
        "plan_key dropped a Knobs field — exact/approx cache aliasing")
    approx = dataclasses.replace(Knobs(), stop_eps=0.25, stop_leaves=8)
    assert plan_key(7, Knobs()) != plan_key(7, approx)
    assert plan_key(7, Knobs()) != plan_key(8, Knobs())
    # autotune-resolved knobs are Knobs fields too, so a retune that
    # changes dma_depth re-keys AOT plans AND the result cache
    names = {f.name for f in dataclasses.fields(Knobs)}
    assert "dma_depth" in names, names
    tuned = dataclasses.replace(Knobs(), dma_depth=2)
    assert plan_key(7, Knobs()) != plan_key(7, tuned)


# --------------------------------------------------------------------- #
# update(): one atomic epoch publish under a stable id
# --------------------------------------------------------------------- #
def test_facade_update_is_stable_and_searchable(data):
    walks, extra, _ = data
    ix = _make_index(data)
    n = ix.n_series
    new_row = random_walk(1, L, seed=91)[0]
    ix.update(5, new_row)
    assert ix.n_series == n                      # delete + add, net zero
    d, i = ix.search(jnp.asarray(new_row[None]), k=1)
    assert int(np.asarray(i).ravel()[0]) == 5    # stable id survived
    # a second update re-routes through the alias to the same stable id
    ix.update(5, random_walk(1, L, seed=92)[0])
    assert ix.n_series == n
    ids = np.asarray(ix.search(jnp.asarray(walks[:1]), k=n)[1]).ravel()
    assert (ids == 5).sum() == 1
    with pytest.raises(ValueError):
        ix.update(5, np.zeros((3, L), np.float32))   # not one row


def test_engine_update_atomic_under_concurrent_readers(data):
    walks = random_walk(48, 32, seed=61)
    ix = FreshIndex.build(walks, IndexConfig(leaf_capacity=8))
    q = jnp.asarray(walks[:2])
    sid, n, errors = 5, 48, []
    stop = threading.Event()
    with ix.engine(EngineConfig(max_batch=4, linger_ms=0.0)) as eng:
        eng.submit(q, k=n).result()              # warm the plan

        def reader():
            while not stop.is_set():
                ids = np.asarray(eng.submit(q, k=n).result()[1])
                for r in range(ids.shape[0]):
                    c = int((ids[r] == sid).sum())
                    if c != 1:
                        errors.append(c)
                        return

        t = threading.Thread(target=reader)
        t.start()
        try:
            for step in range(12):
                eng.update(sid, random_walk(1, 32, seed=100 + step)[0])
        finally:
            stop.set()
            t.join()
    assert not errors, (
        f"reader observed {errors[0]} live rows for stable id {sid} "
        f"mid-update — the delete+add pair was published non-atomically")


# --------------------------------------------------------------------- #
# engine latency tiers: keyed apart, measured apart
# --------------------------------------------------------------------- #
def test_engine_tiers_share_nothing_and_report_quality(calibrated):
    ix, hq, _ = calibrated
    q = jnp.asarray(hq[:4])
    cfg = EngineConfig(max_batch=4, linger_ms=0.0, cache_entries=64,
                       latency_tiers={"batch": TARGET})
    with ix.engine(cfg) as eng:
        d_e, i_e = eng.submit(q, k=10).result()
        # same queries through the approx tier: the epoch-keyed result
        # cache holds the exact rows — a key collision would replay them
        d_a, i_a = eng.submit(q, k=10, priority="batch").result()
        d_f, i_f = ix.search(q, k=10, mode="approx", recall_target=TARGET)
        assert np.array_equal(np.asarray(i_a), np.asarray(i_f))
        assert np.array_equal(np.asarray(d_a), np.asarray(d_f))
        assert np.array_equal(np.asarray(i_e),
                              np.asarray(ix.search(q, k=10)[1]))
        st = eng.stats()["quality"]
        tiers = st["tiers"] if "tiers" in st else st
        approx = [v for name, v in tiers.items()
                  if isinstance(v, dict) and name.startswith("approx")]
        assert approx and approx[0]["queries"] >= 4
    with pytest.raises(ValueError):
        EngineConfig(latency_tiers={"interactive": 1.5})
    with pytest.raises(ValueError):
        EngineConfig(latency_tiers={"nope": "exact"})
