"""The plain reference agrees with a tiny numpy brute force, and the
comparison reads what it should."""

import numpy as np
import pytest

from bench import data, reference


def _numpy_knn(x, q, k):
    def zn(a):
        a = a.astype(np.float64)
        a = a - a.mean(-1, keepdims=True)
        return a / (a.std(-1, keepdims=True) + 1e-8)
    d = np.sqrt(((zn(x)[None] - zn(q)[:, None]) ** 2).sum(-1))
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(d, ids, axis=1), ids


@pytest.fixture(scope="module")
def tiny():
    """One part: all 2048 series as one array."""
    x = data.walks(3, data.DATA, 2048, 64)
    q = np.asarray(data.walks(3, data.QUERIES, 40, 64))
    return [(0, lambda: x)], q


def test_reference_matches_numpy(tiny):
    x, q = tiny
    ref = reference.Reference(x, q, 10)
    d, ids = _numpy_knn(np.asarray(x[0][1]()), q, 10)
    np.testing.assert_allclose(ref.dist, d, rtol=1e-12)
    assert np.array_equal(ref.ids, ids)
    assert ref.unsure == 0


def test_exact_answer_reads_nought(tiny):
    x, q = tiny
    ref = reference.Reference(x, q, 10)
    dist_err, rank_gap = reference.compare(
        x, q, ref, ref.dist.astype(np.float32), ref.ids)
    assert dist_err.max() < 1e-7
    assert rank_gap.max() == 0.0


@pytest.mark.parametrize("fault", ["swap_id", "repeat_id", "bad_id",
                                   "distance", "short"])
def test_faulty_answer_reads_beyond_any_limit(tiny, fault):
    x, q = tiny
    ref = reference.Reference(x, q, 10)
    d, i = ref.dist.astype(np.float32).copy(), ref.ids.copy()
    if fault == "swap_id":
        i[5, 3] = ref.ids[6, 3]
    elif fault == "repeat_id":
        i[5, 3] = i[5, 2]
    elif fault == "bad_id":
        i[5, 3] = -1
    elif fault == "distance":
        d[5, 3] *= 1.0001
    else:
        d, i = d[:, :9], i[:, :9]
    dist_err, rank_gap = reference.compare(x, q, ref, d, i)
    assert max(dist_err.max(), rank_gap.max()) > 5e-5
    if fault != "short":
        assert np.argmax(np.maximum(dist_err, rank_gap)) == 5


def test_control_reads_above_the_reference(tiny):
    x, q = tiny
    ref = reference.Reference(x, q, 10)
    d, i = reference.control_scan(x, q, 10)
    dist_err, _ = reference.compare(x, q, ref, d, i)
    assert dist_err.max() > 1e-6


#: sha256 of the parent's `Reference(x, q, 10)` (float64 distances, then
#: ids as int64) and of its `control_scan(x, q, 10)` (the same), where x
#: is the 4096 x 64 series of seed 2**31 + 77 and q the 40 queries of
#: seed 3, computed on the CPU before the reference took parts
PINNED_REFERENCE = ("2cde48dd7321e09d27e72913615cd546"
                    "ae2e35368101fcba0c8ad7fc0711ec89")
PINNED_CONTROL = ("d0b0a1ffef212524ca5652a6969c00e6"
                  "0d361cfdccaf3f0f2cbdb3d8b9ffcd97")


def _digest(d, i):
    import hashlib
    return hashlib.sha256(np.asarray(d, np.float64).tobytes()
                          + np.asarray(i, np.int64).tobytes()).hexdigest()


@pytest.fixture(scope="module")
def pinned():
    x = data.walks(2**31 + 77, data.DATA, 4096, 64)
    return x, np.asarray(data.walks(3, data.QUERIES, 40, 64))


def test_one_part_keeps_the_answers_of_one_array(pinned):
    x, q = pinned
    one = [(0, lambda: x)]
    ref = reference.Reference(one, q, 10)
    assert _digest(ref.dist, ref.ids) == PINNED_REFERENCE
    assert ref.unsure == 0
    assert _digest(*reference.control_scan(one, q, 10)) == PINNED_CONTROL


def _split(x, sizes, made=None):
    """x as lazy parts of `sizes` rows; `made` collects a weak reference
    to each part made, and each make checks that the ones before it are
    gone."""
    import weakref
    out, first = [], 0
    for n in sizes:
        def make(first=first, n=n):
            if made is not None:
                assert all(r() is None for r in made), "two parts resident"
            rows = x[first:first + n]
            if made is not None:
                made.append(weakref.ref(rows))
            return rows
        out.append((first, make))
        first += n
    return out


def test_parts_give_the_answers_of_one_array(pinned):
    x, q = pinned
    one = [(0, lambda: x)]
    ref = reference.Reference(one, q, 10)
    ctl = reference.control_scan(one, q, 10)
    for sizes in ([1024] * 4, [512, 2048, 1536]):
        parts = _split(x, sizes)
        got = reference.Reference(parts, q, 10)
        assert np.array_equal(got.ids, ref.ids)
        assert np.array_equal(got.dist, ref.dist)
        assert got.unsure == ref.unsure
        d, i = reference.control_scan(parts, q, 10)
        assert np.array_equal(i, ctl[1]) and np.array_equal(d, ctl[0])
        for a, b in zip(reference.compare(parts, q, got, *ctl),
                        reference.compare(one, q, ref, *ctl)):
            assert np.array_equal(a, b)


def test_parts_are_resident_one_at_a_time(pinned):
    x, q = pinned
    made = []
    parts = _split(x, [1024] * 4, made)
    ref = reference.Reference(parts, q, 10)
    d, i = reference.control_scan(parts, q, 10)
    dist_err, _ = reference.compare(parts, q, ref, d, i)
    assert len(made) == 3 * 4 and dist_err.max() > 1e-6
    # an id that no part holds is an answer beyond any limit
    i = i.copy()
    i[7, 2] = 4096
    dist_err, rank_gap = reference.compare(parts, q, ref, d, i)
    assert dist_err[7] == rank_gap[7] == np.inf
    assert np.isfinite(np.delete(rank_gap, 7)).all()
