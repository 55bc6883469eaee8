"""The readers of the search plan's counters: they count the window's
`fresh.search` spans and read as many of the ring's newest records, and
read nothing where the records cannot cover the spans."""

import json
import sys

import numpy as np
import pytest

from bench import harness, tracing

from .conftest import BENCH

METRICS = ("rounds_per_query.batch", "live_round_share.batch",
           "refine_slot_share.batch")
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


def read(name, r):
    return harness.load_module(BENCH, "metrics", name).read(r)


def span(start, name="fresh.search"):
    return (name, start, 1000)


def test_only_the_windows_searches_count(obs):
    # a warm-up search before the window: its span lies outside
    obs.record(128, K, np.array([500, 100, 100], np.int32))
    # the window's three searches: (queries, rounds, live, refined)
    calls = [(128, 10, 1000, 6000), (128, 20, 1500, 9000),
             (64, 30, 1200, 4000)]
    for q, rounds, live, refined in calls:
        obs.record(q, K, np.array([rounds, live, refined], np.int32))
    host = [span(500_000), span(2_000_000), span(4_000_000),
            span(8_999_000), ("bench.search", 2_000_000, 10_000),
            span(9_500_000, "fresh.search.prepare")]
    r = reading(host)
    qr = sum(q * rounds for q, rounds, _, _ in calls)
    assert read("rounds_per_query.batch", r) == pytest.approx(
        qr / sum(c[0] for c in calls))
    assert read("live_round_share.batch", r) == pytest.approx(
        100.0 * sum(c[2] for c in calls) / qr)
    assert read("refine_slot_share.batch", r) == pytest.approx(
        100.0 * sum(c[3] for c in calls) / (K * qr))


def test_nothing_to_read(obs, monkeypatch):
    obs.record(128, K, np.array([10, 1000, 6000], np.int32))
    obs.record(128, K, np.array([20, 1500, 9000], np.int32))
    three = reading([span(2_000_000), span(3_000_000), span(4_000_000)])
    two = reading([span(2_000_000), span(3_000_000)])
    for name in METRICS:
        # spans outnumber records
        assert read(name, three) is None
        # no search in the window
        assert read(name, reading([span(500_000)])) is None
        assert read(name, two) is not None
    # a search whose plan kept no counts (a sharded index)
    obs.record(128, K, None)
    for name in METRICS:
        assert read(name, two) is None
    # a program without the ring reads nothing and raises nothing
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    for name in METRICS:
        assert read(name, two) is None


def test_traced_run_reads_the_counters(tiny_root, run_tiny, obs):
    """A traced run on the CPU: the facade's spans reach the reduced
    trace, and the three readers read what the window's searches
    recorded."""
    path = f"{tiny_root}/BENCHMARK.json"
    with open(path) as f:
        spec = json.load(f)
    for name in METRICS:
        spec["per_layer"].append(
            {"name": name, "unit": "%", "better": "higher",
             "source": "device_trace", "layer": "search plan",
             "moves": "queries_per_s", "workloads": ["tiny-rw64.batch"]})
    with open(path, "w") as f:
        json.dump(spec, f)
    out = run_tiny("tiny-rw64.batch", trace=True)
    assert out["correct"] is True
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(METRICS) <= set(m)
    assert m["rounds_per_query.batch"] >= 1
    assert 0 < m["live_round_share.batch"] <= 100
    assert 0 < m["refine_slot_share.batch"] <= 100
    # every record of the run is the warm-up's or the window's
    recs = obs.records()
    calls = len(recs) - 1
    assert calls >= 1 and all(r.queries == 16 for r in recs)
    want = sum(obs.counts(r)[0] * 16 for r in recs[1:]) / (16 * calls)
    assert m["rounds_per_query.batch"] == pytest.approx(want)
