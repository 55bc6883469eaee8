"""The readers of the sharded search plan: its collectives' device time
per query, and the share of (shard, round) pairs in which a shard had
anything to refine, from the plan's counters."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from bench import harness, tracing

from .conftest import BENCH, ROOT, make_tiny_root

WINDOW = (1_000_000, 9_000_000)
K = 8
SHARDS = 4


@pytest.fixture
def obs():
    from repro import obs
    obs.clear()
    yield obs
    obs.clear()


def reading(host=(), ops=None, queries=0):
    trace = tracing.Trace(ops={0: []} if ops is None else ops,
                          host=list(host), window=WINDOW)
    window = harness.Window(seconds=8.0, attempted=queries, errors=0,
                            metrics={}, queries=None, dist=None, ids=None,
                            counters={"queries": queries})
    return harness.Reading(cell=None, window=window, trace=trace, shapes={},
                           device_kind="TPU v5 lite", bench_dir=BENCH)


def read(metric, r):
    return harness.load_module(BENCH, "metrics", metric).read(r)


def span(start, name="fresh.search"):
    return (name, start, 1000)


def test_collectives_summed_over_chips_per_query():
    us = 1000
    ops = {c: [("%pmin.16 = f32[128]", 2_000_000, 10 * us),
               ("%pmax.24 = s32[128]", 2_100_000, 5 * us),
               ("%all-gather.10 = s32[512,10]", 3_000_000, 2 * us),
               ("%psum.7 = s32[3]", 3_100_000, 1 * us),
               ("%refine_topk.20 = (f32[128,1,128]", 4_000_000, 300 * us),
               ("%fusion.3 = f32[128]", 5_000_000, 7 * us),
               # outside the window
               ("%pmin.16 = f32[128]", 9_500_000, 10 * us)]
           for c in range(SHARDS)}
    got = read("collective_ms_per_query.batch", reading(ops=ops, queries=256))
    assert got == pytest.approx(1e3 * SHARDS * 18e-6 / 256)
    # no collective in the trace (one chip), or no query: nothing to read
    one = {0: [("%refine_topk.20 = (f32[128,1,128]", 2_000_000, 10 * us)]}
    assert read("collective_ms_per_query.batch",
                reading(ops=one, queries=256)) is None
    assert read("collective_ms_per_query.batch",
                reading(ops=ops, queries=0)) is None


def test_shard_live_share_reads_the_windows_searches(obs):
    # a warm-up search before the window, then three in it:
    # (rounds, live, refined, kernel rows, shard live rounds)
    obs.record(128, K, np.array([50, 90, 900, 25_600, 200], np.int32),
               SHARDS)
    calls = [[10, 800, 5000, 5120, 31], [20, 1500, 9000, 10_240, 44],
             [30, 1200, 4000, 15_360, 120]]
    for c in calls:
        obs.record(128, K, np.array(c, np.int32), SHARDS)
    r = reading([span(500_000), span(2_000_000), span(4_000_000),
                 span(8_999_000), ("bench.search", 2_000_000, 10_000)])
    assert read("shard_live_share.batch", r) == pytest.approx(
        100.0 * (31 + 44 + 120) / (SHARDS * 60))


def test_shard_live_share_nothing_to_read(obs, monkeypatch):
    two = reading([span(2_000_000), span(3_000_000)])
    obs.record(128, K, np.array([10, 80, 500, 5120, 40], np.int32), SHARDS)
    assert read("shard_live_share.batch", two) is None   # too few records
    obs.record(128, K, np.array([10, 80, 500, 5120, 20], np.int32), SHARDS)
    assert read("shard_live_share.batch", two) == pytest.approx(75.0)
    assert read("shard_live_share.batch",
                reading([span(500_000)])) is None        # none in window
    # a record of four counts (a program without the fifth), or none
    obs.record(128, K, np.array([10, 80, 500, 5120], np.int32))
    obs.record(128, K, np.array([10, 80, 500, 5120, 20], np.int32), SHARDS)
    assert read("shard_live_share.batch", two) is None
    obs.record(128, K, None)
    obs.record(128, K, np.array([10, 80, 500, 5120, 20], np.int32), SHARDS)
    assert read("shard_live_share.batch", two) is None
    # a ring without the accessor, and a program without the ring
    monkeypatch.delattr(obs, "shard_live_rounds")
    assert read("shard_live_share.batch", two) is None
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert read("shard_live_share.batch", two) is None


SCRIPT = textwrap.dedent("""
    import json, sys, time
    from bench import harness
    from repro import obs
    root = sys.argv[1]
    harness.use_compile_cache = lambda root: "off"
    cell = harness.load_cell("tiny-rw64-4shard.batch", root=root)
    out = harness.run_cell(cell, 2**31 + 4099, 0.5, True, time.monotonic(),
                           require_tpu=False, root=root)
    recs = obs.records()[1:]                 # the window's searches
    out["want"] = 100.0 * sum(obs.shard_live_rounds(r) for r in recs) / sum(
        r.shards * obs.counts(r)[0] for r in recs)
    print(json.dumps(out))
""")


def test_traced_run_of_the_sharded_fixture(tmp_path):
    """A traced run of the fixture's four-shard batch cell on four CPU
    devices: the counter reader reads the window's searches; the CPU
    trace holds no TPU operation, so the collective reader reads
    nothing."""
    root = make_tiny_root(tmp_path)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    cell = "tiny-rw64-4shard.batch"
    spec["per_layer"] += [
        {"name": "shard_live_share.batch", "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "search plan",
         "moves": "queries_per_s", "workloads": [cell]},
        {"name": "collective_ms_per_query.batch", "unit": "ms",
         "better": "lower", "source": "device_trace", "layer": "mesh",
         "moves": "queries_per_s", "workloads": [cell]}]
    with open(path, "w") as f:
        json.dump(spec, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={SHARDS}",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    p = subprocess.run([sys.executable, "-c", SCRIPT, root], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}"
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    got = out["metrics"]["shard_live_share.batch"]["value"]
    assert got == pytest.approx(out["want"])
    assert 0 < got <= 100
    assert "collective_ms_per_query.batch" not in out["metrics"]
