"""FreshIndex facade: the one public surface for build / k-NN search /
incremental add / shard / checkpoint.  k-NN exactness is proven against
the brute-force oracle for k in {1, 5, 10} across all three leaf bounds;
add()+compact() must be indistinguishable from a fresh build; save()/
load() must round-trip search results exactly.  (The sharded path has its
own subprocess test in test_sharded.py.)"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import FreshIndex, IndexConfig
from repro.core import search_bruteforce


@pytest.fixture(scope="module")
def index(walks):
    return FreshIndex.build(walks, IndexConfig(leaf_capacity=64))


# --------------------------------------------------------------------- #
# k-NN exactness vs the oracle
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("k", [1, 5, 10])
def test_knn_matches_bruteforce(index, walks, queries, k):
    q = jnp.asarray(queries)
    d, i = index.search(q, k=k)
    db, ib = search_bruteforce(jnp.asarray(walks), q, k=k)
    expect = (q.shape[0],) if k == 1 else (q.shape[0], k)
    assert d.shape == expect and i.shape == expect
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ib))
    np.testing.assert_allclose(np.asarray(d), np.asarray(db),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bound", ["prefix", "symbox", "paabox"])
@pytest.mark.parametrize("k", [1, 5, 10])
def test_knn_exact_for_every_bound(walks, queries, bound, k):
    sub = walks[:512]
    ix = FreshIndex.build(sub, IndexConfig(leaf_capacity=32, bound=bound))
    q = jnp.asarray(queries[:8])
    d, i = ix.search(q, k=k)
    db, ib = search_bruteforce(jnp.asarray(sub), q, k=k)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ib))
    np.testing.assert_allclose(np.asarray(d), np.asarray(db),
                               rtol=1e-5, atol=1e-5)


def test_knn_distances_ascending(index, queries):
    d, _ = index.search(jnp.asarray(queries), k=10)
    d = np.asarray(d)
    assert np.all(d[:, 1:] >= d[:, :-1] - 1e-7)


def test_max_rounds_capped_is_upper_bound(index, queries):
    q = jnp.asarray(queries[:8])
    d_exact, _ = index.search(q, k=5)
    d_cap, _ = index.search(q, k=5, max_rounds=1)
    assert np.all(np.asarray(d_cap) >= np.asarray(d_exact) - 1e-5)


def test_pallas_backend_agrees_with_ref(walks, queries):
    sub, q = walks[:512], jnp.asarray(queries[:8])
    ref = FreshIndex.build(sub, IndexConfig(leaf_capacity=32))
    pal = FreshIndex.build(sub, IndexConfig(leaf_capacity=32,
                                            backend="pallas"))
    dr, ir = ref.search(q, k=5)
    dp, ip = pal.search(q, k=5)
    np.testing.assert_array_equal(np.asarray(ir), np.asarray(ip))
    np.testing.assert_allclose(np.asarray(dr), np.asarray(dp),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------- #
# incremental add / compact (Jiffy-style batch delta)
# --------------------------------------------------------------------- #
def test_add_visible_before_compact(walks, queries):
    from repro.data.synthetic import random_walk
    base, extra = walks[:1024], random_walk(96, walks.shape[1], seed=21)
    ix = FreshIndex.build(base, IndexConfig(leaf_capacity=32))
    ix.add(extra[:40]).add(extra[40:])
    assert ix.n_pending == 96 and ix.n_series == 1024 + 96
    q = jnp.asarray(queries[:8])
    both = np.concatenate([base, extra])
    for k in (1, 10):
        d, i = ix.search(q, k=k)
        db, ib = search_bruteforce(jnp.asarray(both), q, k=k)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ib))
        np.testing.assert_allclose(np.asarray(d), np.asarray(db),
                                   rtol=1e-5, atol=1e-5)


def test_compact_identical_to_fresh_build(walks, queries):
    from repro.data.synthetic import random_walk
    base, extra = walks[:1024], random_walk(96, walks.shape[1], seed=22)
    ix = FreshIndex.build(base, IndexConfig(leaf_capacity=32))
    ix.add(extra).compact()
    assert ix.n_pending == 0
    fresh = FreshIndex.build(np.concatenate([base, extra]),
                             IndexConfig(leaf_capacity=32))
    q = jnp.asarray(queries[:8])
    d1, i1 = ix.search(q, k=10)
    d2, i2 = fresh.search(q, k=10)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
    np.testing.assert_array_equal(np.asarray(ix.index.perm),
                                  np.asarray(fresh.index.perm))


def test_compact_without_delta_is_noop(index):
    before = index.index
    assert index.compact() is index
    assert index.index is before


def test_concurrent_add_search_snapshot_consistency(walks, queries):
    """The defined semantics of add() racing search(): an in-flight engine
    batch answers on the pre-add snapshot (== brute-force oracle over the
    old data), a post-publish batch sees the new series.  The facade
    itself stays immediate-visibility: FreshIndex.search after add()
    includes the delta."""
    from repro.data.synthetic import random_walk
    base, extra = walks[:512], random_walk(64, walks.shape[1], seed=24)
    ix = FreshIndex.build(base, IndexConfig(leaf_capacity=32))
    q = jnp.asarray(queries[:6])
    with ix.engine(max_batch=8) as eng:
        inflight = eng.submit(queries[:6], k=5)     # bound to epoch 0
        eng.add(extra)                              # publish epoch 1
        later = eng.submit(queries[:6], k=5)
        eng.flush()
        d_old, i_old = inflight.result(timeout=60)
        d_new, i_new = later.result(timeout=60)
    db, ib = search_bruteforce(jnp.asarray(base), q, k=5)
    np.testing.assert_array_equal(i_old, np.asarray(ib))
    both = jnp.asarray(np.concatenate([base, extra]))
    db2, ib2 = search_bruteforce(both, q, k=5)
    np.testing.assert_array_equal(i_new, np.asarray(ib2))
    # the facade sees the delta immediately (unchanged contract)
    d_f, i_f = ix.search(q, k=5)
    np.testing.assert_array_equal(np.asarray(i_f), np.asarray(ib2))


# --------------------------------------------------------------------- #
# save / load
# --------------------------------------------------------------------- #
def test_save_load_roundtrip(tmp_path, walks, queries):
    ix = FreshIndex.build(walks[:512], IndexConfig(leaf_capacity=32,
                                                   bound="paabox"))
    ix.save(str(tmp_path))
    restored = FreshIndex.load(str(tmp_path))
    assert restored.config == ix.config
    q = jnp.asarray(queries[:8])
    d1, i1 = ix.search(q, k=10)
    d2, i2 = restored.search(q, k=10)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))


def test_save_load_preserves_pending_delta(tmp_path, walks, queries):
    from repro.data.synthetic import random_walk
    ix = FreshIndex.build(walks[:512], IndexConfig(leaf_capacity=32))
    ix.add(random_walk(48, walks.shape[1], seed=23))
    ix.save(str(tmp_path))
    restored = FreshIndex.load(str(tmp_path))
    assert restored.n_pending == 48
    q = jnp.asarray(queries[:8])
    d1, i1 = ix.search(q, k=5)
    d2, i2 = restored.search(q, k=5)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), atol=0)


def test_save_load_roundtrip_bfloat16_storage(tmp_path, walks, queries):
    """bf16 series are stored as uint16 bit patterns on disk (np.save
    cannot serialize ml_dtypes) and decoded back on load."""
    ix = FreshIndex.build(walks[:512], IndexConfig(leaf_capacity=32,
                                                   dtype="bfloat16"))
    ix.save(str(tmp_path))
    restored = FreshIndex.load(str(tmp_path))
    assert restored.index.series.dtype == jnp.bfloat16
    q = jnp.asarray(queries[:8])
    d1, i1 = ix.search(q, k=5)
    d2, i2 = restored.search(q, k=5)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))


def test_load_rejects_foreign_checkpoint(tmp_path):
    from repro.checkpoint import save_checkpoint
    save_checkpoint(str(tmp_path), 0, {"w": jnp.ones((2, 2))})
    with pytest.raises(ValueError, match="not a FreshIndex checkpoint"):
        FreshIndex.load(str(tmp_path))


# --------------------------------------------------------------------- #
# config validation — the facade catches mismatches the free functions
# used to let through silently
# --------------------------------------------------------------------- #
def test_config_is_frozen_and_validated():
    with pytest.raises(dataclasses.FrozenInstanceError):
        IndexConfig().__setattr__("bits", 4)
    with pytest.raises(ValueError, match="bound"):
        IndexConfig(bound="nope")
    with pytest.raises(ValueError, match="backend"):
        IndexConfig(backend="cuda")
    with pytest.raises(ValueError, match="dtype"):
        IndexConfig(dtype="int8")
    cfg = IndexConfig(leaf_capacity=32)
    assert IndexConfig.from_dict(cfg.to_dict()) == cfg
    with pytest.raises(ValueError, match="round_leaves"):
        IndexConfig(round_leaves=0)
    with pytest.raises(ValueError, match="pq_budget"):
        IndexConfig(pq_budget=0)
    # the new refinement knobs round-trip through to_dict/from_dict (the
    # checkpoint manifest path) and old manifests without them still load
    cfg = IndexConfig(round_leaves=16, pq_budget=64)
    assert IndexConfig.from_dict(cfg.to_dict()) == cfg
    old = {k: v for k, v in IndexConfig().to_dict().items()
           if k not in ("round_leaves", "pq_budget")}
    assert IndexConfig.from_dict(old) == IndexConfig()


def test_build_rejects_indivisible_series_len():
    with pytest.raises(ValueError, match="not divisible"):
        FreshIndex.build(np.zeros((16, 250), np.float32))


def test_search_rejects_wrong_query_length(index):
    with pytest.raises(ValueError, match="length"):
        index.search(np.zeros((2, 128), np.float32))


def test_search_rejects_bad_k(index):
    with pytest.raises(ValueError, match="k"):
        index.search(np.zeros((1, 256), np.float32), k=0)
    with pytest.raises(ValueError, match="exceeds"):
        index.search(np.zeros((1, 256), np.float32), k=10 ** 9)


def test_prepare_queries_mismatch_raises(index, queries):
    from repro.core.search import prepare_queries
    with pytest.raises(ValueError, match="not divisible"):
        prepare_queries(jnp.ones((2, 250)))
    q, q_paa = prepare_queries(jnp.asarray(queries), index=index.index)
    assert q_paa.shape[-1] == index.index.paa.shape[1]


@pytest.mark.parametrize("Q", [1, 16, 128, 129])
def test_answer_does_not_depend_on_the_batch(index, walks, Q):
    """A query's answer is the same bits whether it rides alone or in a
    batch of 300 (more than two QUERY_TILE tiles): queries are prepared
    and their distances recomputed in fixed tiles, which is what lets an
    engine bucket return the facade's bits on TPU."""
    from repro.data.synthetic import query_workload
    q = jnp.asarray(query_workload(walks, 300, noise_sigma=0.05, seed=5))
    d_all, i_all = index.search(q, k=5)
    d, i = index.search(q[-Q:], k=5)
    np.testing.assert_array_equal(np.asarray(d), np.asarray(d_all)[-Q:])
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_all)[-Q:])
