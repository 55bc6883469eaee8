"""The harness finds a cell's files by name, runs it end to end at test
size, prints the result line the contract asks for, and refuses to run
without a chip."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness

from .conftest import BENCH, ROOT

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def test_cell_files_are_found_by_name(tiny_root):
    cell = harness.load_cell("tiny-rw64.batch", root=tiny_root)
    assert cell.config["name"] == "tiny-rw64"
    assert cell.mix["loop"] == "closed_batch"
    assert [m["name"] for m in cell.end_to_end] == ["queries_per_s",
                                                    "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["answered.tiny"]
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell", root=tiny_root)


@pytest.mark.parametrize("cell,e2e", [
    ("tiny-rw64.batch", {"queries_per_s", "setup_s"}),
    ("tiny-rw64.serve", {"latency_p95_ms", "served_queries_per_s",
                         "setup_s"}),
])
def test_untraced_run_reports_its_end_to_end_metrics(run_tiny, cell, e2e):
    out = run_tiny(cell)
    assert list(out) == KEYS + ["checks"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == e2e
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert set(out["checks"]) == {"dist_err", "rank_gap"}
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]
    json.dumps(out)


@pytest.mark.parametrize("cell,layers", [
    ("tiny-rw64.batch", {"answered.tiny"}),
    ("tiny-rw64.serve", {"batch_fill.serve", "rounds_per_query.serve"}),
])
def test_traced_run_reports_its_per_layer_metrics(run_tiny, cell, layers):
    out = run_tiny(cell, trace=True)
    assert list(out) == KEYS + ["breakdown", "checks"]
    assert out["correct"] is True
    assert set(out["metrics"]) == layers
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "rw256-4m.batch-exact", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_run_exits_nonzero_without_a_chip():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run_py(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
