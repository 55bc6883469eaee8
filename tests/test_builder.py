"""IndexBuilder: the Refresh-driven build pipeline (paper §IV-V).

The load-bearing property is schedule-independence: a multi-worker build
under crash/delay injectors, a streaming chunked feed, and the sequential
single-shot `FreshIndex.build` must all produce BIT-IDENTICAL FlatIndex
arrays — and the fused one-program `build_index` must agree too.
Compaction is the same machinery: `merge_sorted_delta` consumes the
stored core arrays as-is, so repeated compacts are drift-free even with
half-precision storage (compact∘compact == compact).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.api import FreshIndex, IndexConfig
from repro.core import (IndexBuilder, build_index, merge_sorted_delta,
                        search_bruteforce)
from repro.core.refresh import Injectors
from repro.data.synthetic import random_walk


def _assert_bit_identical(a, b, context=""):
    for f in a._fields:
        x, y = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        assert x.dtype == y.dtype, (context, f, x.dtype, y.dtype)
        # ml_dtypes halves compare exactly via their bit patterns
        if x.dtype.itemsize == 2 and x.dtype.kind != "u":
            x, y = x.view(np.uint16), y.view(np.uint16)
        np.testing.assert_array_equal(x, y, err_msg=f"{context}: {f}")


@pytest.fixture(scope="module")
def small(walks):
    return walks[:1024]


@pytest.fixture(scope="module")
def reference(small):
    return FreshIndex.build(small, IndexConfig(leaf_capacity=32))


# --------------------------------------------------------------------- #
# the host-side key machinery == the device key (bit-identity foundation)
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("bits,segments", [(8, 16), (4, 8), (3, 5)])
def test_interleaved_key_np_matches_jnp(bits, segments):
    """The numpy key mirror the builder's sort/merge phases use must be
    bit-identical to the device key, its stable lexsort must equal
    jnp.lexsort's permutation and the device build's `lexsort_lanes`
    (ties keep input order), and the byte-packed scalar key (the merge
    path's binary-search key) must order exactly like the lane tuple.
    (Lives here, not in test_isax.py: that module skips without
    hypothesis, and these properties must run in CI.)"""
    from repro.core import isax
    rng = np.random.default_rng(3)
    words = rng.integers(0, 1 << bits, size=(257, segments), dtype=np.uint8)
    kj = np.asarray(isax.interleaved_key(jnp.asarray(words), bits))
    kn = isax.interleaved_key_np(words, bits)
    np.testing.assert_array_equal(kj, kn)
    lanes = [jnp.asarray(kj[:, i]) for i in range(kj.shape[1])]
    perm_j = np.asarray(jnp.lexsort(tuple(reversed(lanes))))
    np.testing.assert_array_equal(perm_j, isax.lexsort_keys(kn))
    np.testing.assert_array_equal(
        np.asarray(isax.lexsort_lanes(jnp.asarray(kj))), perm_j)
    packed = isax.pack_keys_bytes(kn)
    np.testing.assert_array_equal(np.argsort(packed, kind="stable"),
                                  isax.lexsort_keys(kn))


# --------------------------------------------------------------------- #
# the single-shot paths agree: builder pipeline == fused device program
# --------------------------------------------------------------------- #
def test_pipeline_matches_fused_build(small, reference):
    fused = build_index(jnp.asarray(small), leaf_capacity=32)
    _assert_bit_identical(reference.index, fused, "pipeline vs fused")


# --------------------------------------------------------------------- #
# multi-worker builds under injectors: bit-identical, still terminate
# --------------------------------------------------------------------- #
def test_multiworker_crash_build_bit_identical(small, reference):
    """4 workers, 3 of them crash permanently after one payload each —
    the surviving worker (plus the calling thread, if need be) helps
    every phase to completion and the result is bit-identical."""
    b = IndexBuilder(IndexConfig(leaf_capacity=32), workers=4,
                     part_rows=128,
                     injectors=Injectors.crashing({1, 2, 3}, after=1))
    ix = b.feed(small).finalize()
    _assert_bit_identical(ix.index, reference.index, "crash build")
    rep = b.report()
    assert rep["workers"] == 4
    crashed = sum(p["crashed_workers"] for p in rep["phases"].values())
    helped = sum(p["helped_parts"] for p in rep["phases"].values())
    assert crashed >= 3, rep
    assert helped > 0, rep
    apps = sum(p["applications"] for p in rep["phases"].values())
    parts = sum(p["parts"] for p in rep["phases"].values())
    assert apps >= parts  # helping may duplicate, never skip


def test_all_workers_crash_still_completes(small, reference):
    """Even with EVERY worker crashed at its first payload, finalize()
    terminates (traverse_complete: the caller helps) — the strongest
    form of the paper's progress property we can state."""
    b = IndexBuilder(IndexConfig(leaf_capacity=32), workers=4,
                     part_rows=256,
                     injectors=Injectors.crashing({0, 1, 2, 3}, after=0))
    ix = b.feed(small).finalize()
    _assert_bit_identical(ix.index, reference.index, "all-crash build")


def test_multiworker_delay_build_bit_identical(small, reference):
    b = IndexBuilder(IndexConfig(leaf_capacity=32), workers=4,
                     part_rows=128,
                     injectors=Injectors.delaying(0.002, worker_ids={0},
                                                  every=2))
    ix = b.feed(small).finalize()
    _assert_bit_identical(ix.index, reference.index, "delay build")


# --------------------------------------------------------------------- #
# streaming feed: N chunks == one-shot, and the result answers exactly
# --------------------------------------------------------------------- #
def test_feed_chunks_equals_oneshot(small, reference, queries):
    b = FreshIndex.builder(IndexConfig(leaf_capacity=32))
    for lo in range(0, small.shape[0], 192):       # ragged, non-part-sized
        b.feed(small[lo:lo + 192])
    ix = b.finalize()
    _assert_bit_identical(ix.index, reference.index, "chunked feed")
    q = jnp.asarray(queries[:8])
    for k in (1, 5, 10):
        d, i = ix.search(q, k=k)
        db, ib = search_bruteforce(jnp.asarray(small), q, k=k)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ib))
        np.testing.assert_allclose(np.asarray(d), np.asarray(db),
                                   rtol=1e-5, atol=1e-5)


def test_feed_is_eager_for_complete_blocks(small):
    """Streaming ingest: summarize/key/sort run at feed() time for every
    complete part_rows block, not all at finalize()."""
    b = IndexBuilder(IndexConfig(leaf_capacity=32), part_rows=256)
    b.feed(small[:600])
    rep = b.report()
    assert rep["phases"]["summarize"]["parts"] == 2      # 600 // 256
    assert rep["phases"]["sort"]["parts"] == 2
    assert rep["phases"]["merge"]["parts"] == 0          # finalize-only
    b.feed(small[600:]).finalize()
    assert b.report()["phases"]["merge"]["parts"] > 0


def test_feed_copies_reused_caller_buffer(small, reference):
    """Read-into-buffer streaming: the caller refills ONE buffer between
    feeds.  The builder must not alias it (tail rows outlive the call)."""
    b = IndexBuilder(IndexConfig(leaf_capacity=32), part_rows=256)
    buf = np.empty((100, 256), np.float32)
    for lo in range(0, small.shape[0], 100):
        chunk = small[lo:lo + 100]
        buf[:chunk.shape[0]] = chunk
        b.feed(buf[:chunk.shape[0]])
        buf[:] = np.nan                          # caller reuses the buffer
    ix = b.finalize()
    _assert_bit_identical(ix.index, reference.index, "reused feed buffer")


def test_add_copies_reused_caller_buffer(walks, queries):
    """FreshIndex.add must own its delta rows for the same reason."""
    base = walks[:512]
    extra = random_walk(32, 256, seed=36)
    ix = FreshIndex.build(base, IndexConfig(leaf_capacity=32))
    buf = np.array(extra[:16])
    ix.add(buf)
    buf[:] = np.nan
    ix.add(extra[16:])                           # invalidates delta_cat
    ix.compact()
    fresh = FreshIndex.build(np.concatenate([base, extra]),
                             IndexConfig(leaf_capacity=32))
    _assert_bit_identical(ix.index, fresh.index, "reused add buffer")


def test_builder_validation():
    b = IndexBuilder(IndexConfig(leaf_capacity=32))
    with pytest.raises(ValueError, match="no data fed"):
        b.finalize()
    with pytest.raises(ValueError, match="not divisible"):
        b.feed(np.zeros((4, 250), np.float32))
    b.feed(np.zeros((4, 256), np.float32))
    with pytest.raises(ValueError, match="series length"):
        b.feed(np.zeros((4, 128), np.float32))
    b.finalize()
    with pytest.raises(RuntimeError, match="finalize"):
        b.feed(np.zeros((4, 256), np.float32))
    with pytest.raises(RuntimeError, match="finalize"):
        b.finalize()
    with pytest.raises(ValueError, match="part_rows"):
        IndexBuilder(IndexConfig(), part_rows=0)


# --------------------------------------------------------------------- #
# incremental compaction: stored arrays consumed as-is
# --------------------------------------------------------------------- #
def _rows_by_id(flat):
    """Index arrays keyed by original series id (bit-comparable dict)."""
    perm = np.asarray(flat.perm)
    v = perm >= 0
    order = np.argsort(perm[v])
    series = np.asarray(flat.series)[v][order]
    if series.dtype.itemsize == 2:
        series = series.view(np.uint16)
    return (series, np.asarray(flat.paa)[v][order],
            np.asarray(flat.words)[v][order],
            np.asarray(flat.sq_norms)[v][order])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "float16"])
def test_compact_preserves_stored_core_bits(walks, dtype):
    """The documented low-precision drift is gone: compact() keeps every
    already-stored row's series/paa/words/sq_norms bit-identical — no
    re-normalization, no re-rounding through float32."""
    base = walks[:512]
    cfg = IndexConfig(leaf_capacity=32, dtype=dtype)
    ix = FreshIndex.build(base, cfg)
    before = _rows_by_id(ix.index)
    ix.add(random_walk(40, 256, seed=31)).compact()
    after = _rows_by_id(ix.index)
    for b, a in zip(before, after):
        np.testing.assert_array_equal(b, a[:512])


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_compact_compact_equals_compact(walks, dtype):
    """compact∘compact == compact: splitting the same adds over two
    compacts is bit-identical to one compact (each row rounds through
    the storage dtype exactly once, at ITS first compact), and a compact
    with an empty delta is a no-op."""
    base = walks[:512]
    cfg = IndexConfig(leaf_capacity=32, dtype=dtype)
    b1 = random_walk(40, 256, seed=32)
    b2 = random_walk(56, 256, seed=33)

    two = FreshIndex.build(base, cfg)
    two.add(b1).compact()
    two.add(b2).compact()

    one = FreshIndex.build(base, cfg)
    one.add(b1).add(b2).compact()

    _assert_bit_identical(two.index, one.index, f"{dtype} split compacts")
    before = two.index
    assert two.compact() is two                  # empty delta: no-op
    assert two.index is before


def test_compact_matches_fresh_build_f32(walks, queries):
    """float32 storage: the incremental merge is bit-identical to a fresh
    build over the concatenation (stronger than the facade-level test in
    test_api.py — every array, not just perm/search results)."""
    base, extra = walks[:512], random_walk(64, 256, seed=34)
    ix = FreshIndex.build(base, IndexConfig(leaf_capacity=32))
    ix.add(extra).compact()
    fresh = FreshIndex.build(np.concatenate([base, extra]),
                             IndexConfig(leaf_capacity=32))
    _assert_bit_identical(ix.index, fresh.index, "merge vs fresh")


def test_empty_build_then_add_compact_bootstrap(walks, queries):
    """FreshIndex.build over a (0, L) array is legal (the bootstrap
    pattern): the empty core merges its first delta on compact() and
    answers bit-identically to a direct build."""
    data = walks[:256]
    ix = FreshIndex.build(np.empty((0, 256), np.float32),
                          IndexConfig(leaf_capacity=32))
    assert ix.n_series == 0
    ix.add(data).compact()
    direct = FreshIndex.build(data, IndexConfig(leaf_capacity=32))
    _assert_bit_identical(ix.index, direct.index, "bootstrap build")
    q = jnp.asarray(queries[:4])
    d, i = ix.search(q, k=5)
    db, ib = search_bruteforce(jnp.asarray(data), q, k=5)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ib))


def test_merge_sorted_delta_direct_and_empty(walks):
    cfg = IndexConfig(leaf_capacity=32)
    ix = FreshIndex.build(walks[:256], cfg)
    assert merge_sorted_delta(ix.index, np.zeros((0, 256), np.float32),
                              cfg) is ix.index
    with pytest.raises(ValueError, match="delta must be"):
        merge_sorted_delta(ix.index, np.zeros((4,), np.float32), cfg)


def test_reconstruct_data_is_gone():
    """compact() no longer reconstructs the dataset into original id
    order for a from-scratch rebuild (the merge consumes the stored
    leaf-ordered arrays directly)."""
    assert not hasattr(FreshIndex, "_reconstruct_data")


# --------------------------------------------------------------------- #
# serving: auto-compaction reuses the merge primitive
# --------------------------------------------------------------------- #
def test_engine_auto_compact(walks, queries):
    base = walks[:512]
    ix = FreshIndex.build(base, IndexConfig(leaf_capacity=32))
    extra = random_walk(48, 256, seed=35)
    q = jnp.asarray(queries[:6])
    with ix.engine(max_batch=8, auto_compact_rows=40) as eng:
        eng.add(extra[:24])                      # below threshold: delta
        assert ix.n_pending == 24
        eng.add(extra[24:])                      # 48 >= 40: auto-compact
        assert ix.n_pending == 0
        fut = eng.submit(queries[:6], k=5)
        eng.flush()
        d, i = fut.result(timeout=60)
        st = eng.stats()
    assert st["compactions"] == 1
    both = jnp.asarray(np.concatenate([base, extra]))
    db, ib = search_bruteforce(both, q, k=5)
    np.testing.assert_array_equal(i, np.asarray(ib))
