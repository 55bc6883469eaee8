"""The traffic generators give the same inputs for a seed."""

import numpy as np

from bench import data
from bench.loops import open_poisson

MIX = {"rate_per_s": 70, "arrival_seed": 20231012}


def test_walks_repeat_for_a_seed():
    a = np.asarray(data.walks(2**31 + 5, data.QUERIES, 16, 64))
    b = np.asarray(data.walks(2**31 + 5, data.QUERIES, 16, 64))
    c = np.asarray(data.walks(2**31 + 6, data.QUERIES, 16, 64))
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)


def test_seeds_above_32_bits_keep_their_high_word():
    a = np.asarray(data.walks(5, data.DATA, 4, 64))
    b = np.asarray(data.walks(5 + 2**33, data.DATA, 4, 64))
    assert not np.array_equal(a, b)


def test_streams_of_one_seed_differ():
    a = np.asarray(data.walks(9, data.DATA, 8, 64))
    b = np.asarray(data.walks(9, data.QUERIES, 8, 64))
    assert not np.array_equal(a, b)


def test_arrivals_repeat_for_a_seed():
    a = open_poisson.arrivals(MIX, 2**32 + 3, 20.0)
    b = open_poisson.arrivals(MIX, 2**32 + 3, 20.0)
    assert np.array_equal(a, b)


def test_seeds_reorder_one_set_of_gaps():
    a = open_poisson.arrivals(MIX, 1, 20.0)
    b = open_poisson.arrivals(MIX, 2, 20.0)
    assert a.shape == b.shape == (1400,)
    assert not np.array_equal(a, b)
    assert np.all(np.diff(a) > 0) and 0 < a[0] and a[-1] < 20.0
    # the same gaps, in another order: only the one left out differs
    ga = np.round(np.diff(a, prepend=0.0), 9)
    gb = np.round(np.diff(b, prepend=0.0), 9)
    assert np.intersect1d(ga, gb).size >= a.size - 1


def test_queries_are_one_set_and_the_seed_orders_them():
    mix = {"query_seed": 2**31 + 11}
    a = data.queries(mix, 64, 32)
    assert np.array_equal(a, data.queries(mix, 64, 32))
    assert not np.array_equal(a, data.queries({"query_seed": 3}, 64, 32))
    assert np.array_equal(data.order(2**32 + 1, 64), data.order(2**32 + 1, 64))
    assert not np.array_equal(data.order(1, 64), data.order(2, 64))
    assert np.array_equal(np.sort(data.order(1, 64)), np.arange(64))


def test_block_order_keeps_each_block_whole():
    a, b = data.order(1, 64, 8), data.order(2**32 + 7, 64, 8)
    assert not np.array_equal(a, b)
    for o in (a, b, data.order(3, 20, 8)):
        n = o.size
        assert np.array_equal(np.sort(o), np.arange(n))
        for s in range(0, n, 8):
            assert set(o[s:s + 8]) == set(range(s, min(s + 8, n)))
