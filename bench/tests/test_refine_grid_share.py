"""The reader of the refine grid's working share: the window's refined
pairs over the rows its refine calls ran with times K, from the search
plan's counters; nothing where the records hold no row count."""

import json
import sys

import numpy as np
import pytest

from bench import harness, tracing

from .conftest import BENCH

METRIC = "refine_grid_share.batch"
K = 8
WINDOW = (1_000_000, 9_000_000)


@pytest.fixture
def obs():
    from repro import obs
    obs.clear()
    yield obs
    obs.clear()


def reading(host):
    trace = tracing.Trace(ops={0: []}, host=host, window=WINDOW)
    return harness.Reading(cell=None, window=None, trace=trace, shapes={},
                           device_kind="TPU v5 lite", bench_dir=BENCH)


def read(r):
    return harness.load_module(BENCH, "metrics", METRIC).read(r)


def span(start, name="fresh.search"):
    return (name, start, 1000)


def test_reads_the_windows_searches(obs):
    # a warm-up search before the window: its span lies outside
    obs.record(128, K, np.array([500, 100, 100, 64_000], np.int32))
    # the window's searches: (queries, rounds, live, refined, kernel rows)
    calls = [(128, 10, 1000, 6000, 1200), (128, 20, 1500, 9000, 1800),
             (64, 30, 1200, 4000, 900)]
    for q, *c in calls:
        obs.record(q, K, np.array(c, np.int32))
    r = reading([span(500_000), span(2_000_000), span(4_000_000),
                 span(8_999_000), ("bench.search", 2_000_000, 10_000)])
    assert read(r) == pytest.approx(
        100.0 * sum(c[3] for c in calls) / (K * sum(c[4] for c in calls)))


def test_nothing_to_read(obs, monkeypatch):
    two = reading([span(2_000_000), span(3_000_000)])
    obs.record(128, K, np.array([10, 1000, 6000, 1280], np.int32))
    assert read(two) is None                     # spans outnumber records
    obs.record(128, K, np.array([20, 1500, 9000, 2560], np.int32))
    assert read(two) == pytest.approx(100.0 * 15000 / (K * 3840))
    assert read(reading([span(500_000)])) is None    # no search in window
    obs.record(128, K, None)                     # a sharded index
    obs.record(128, K, np.array([20, 1500, 9000, 2560], np.int32))
    assert read(two) is None
    # a ring without the row accessor, and a program without the ring
    monkeypatch.delattr(obs, "kernel_rows")
    assert read(two) is None
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert read(two) is None


def test_traced_run_reads_the_share(tiny_root, run_tiny, obs):
    """A traced run of the fixture's batch cell on the CPU: 16 queries a
    search, so the plan refines in phases of 16 and 8 rows."""
    path = f"{tiny_root}/BENCHMARK.json"
    with open(path) as f:
        spec = json.load(f)
    spec["per_layer"].append(
        {"name": METRIC, "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "search plan",
         "moves": "queries_per_s", "workloads": ["tiny-rw64.batch"]})
    with open(path, "w") as f:
        json.dump(spec, f)
    out = run_tiny("tiny-rw64.batch", trace=True)
    assert out["correct"] is True
    got = out["metrics"][METRIC]["value"]
    recs = obs.records()[1:]                     # the window's searches
    refined = sum(obs.counts(r)[2] for r in recs)
    steps = sum(obs.kernel_rows(r) * r.round_leaves for r in recs)
    assert got == pytest.approx(100.0 * refined / steps)
    assert 0 < got <= 100
    for r in recs:
        rounds = obs.counts(r)[0]
        assert obs.counts(r)[1] <= obs.kernel_rows(r) <= 16 * rounds
