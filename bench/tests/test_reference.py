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
    x = data.walks(3, data.DATA, 2048, 64)
    q = np.asarray(data.walks(3, data.QUERIES, 40, 64))
    return x, q


def test_reference_matches_numpy(tiny):
    x, q = tiny
    ref = reference.Reference(x, q, 10)
    d, ids = _numpy_knn(np.asarray(x), q, 10)
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
