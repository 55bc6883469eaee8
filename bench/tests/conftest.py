"""Fixtures of the benchmark's CPU tests: a checkout of the benchmark at
test size, in which the fixture's own configuration, mixes and metric
sit beside the real ones as files alone."""

import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
FIXTURES = os.path.join(HERE, "fixtures")

for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """A copy of bench/ with the fixture's files added and the fixture's
    BENCHMARK.json at its root.  The persistent compile cache stays off
    in the test process."""
    from bench import harness
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    for kind in ("configs", "traffic", "metrics"):
        for name in os.listdir(os.path.join(FIXTURES, kind)):
            shutil.copy(os.path.join(FIXTURES, kind, name), bench / kind)
    shutil.copy(os.path.join(FIXTURES, "BENCHMARK.json"), tmp_path)
    monkeypatch.setattr(harness, "use_compile_cache", lambda root: "off")
    return str(tmp_path)


@pytest.fixture
def run_tiny(tiny_root):
    """run_tiny(cell, trace=False, seed=...) -> the result line (a dict)
    of one run of a fixture cell on the CPU."""
    import time

    from bench import harness

    def run(cell, trace=False, seed=2**31 + 77, seconds=0.5):
        c = harness.load_cell(cell, root=tiny_root)
        return harness.run_cell(c, seed, seconds, trace, time.monotonic(),
                                require_tpu=False, root=tiny_root)
    return run
