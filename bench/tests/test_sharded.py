"""A deployment in row shards, one a device, through the whole harness
on four CPU devices: the fixture's `tiny-rw64-4shard` (4 x 2^10 series
of length 64, "shards": 4) under its batch and serve mixes.

The runs need four devices, and the pytest process keeps one, so they
run once, in one subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=4, which prints what
each test reads as its last line.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from .conftest import ROOT, make_tiny_root

SHARDS, ROWS = 4, 1024

SCRIPT = textwrap.dedent("""
    import json, sys, time, weakref
    import numpy as np
    import jax
    import jax.numpy as jnp
    from bench import control, data, harness, sweep
    from repro.api import FreshIndex

    root = sys.argv[1]
    harness.use_compile_cache = lambda root: "off"
    SEED = 2**31 + 4077
    out = {"made": [], "setup": [], "overlap": False}

    # set-up's series, block s on device s; the blocks the reference
    # makes on the default device, one at a time
    made_by_reference = []
    walk_block, sharded_walks = data.walk_block, data.sharded_walks

    def recorded(seed, stream, block, rows, length):
        out["overlap"] |= any(r() is not None for r in made_by_reference)
        x = walk_block(seed, stream, block, rows, length)
        made_by_reference.append(weakref.ref(x))
        out["made"].append([block, sorted(d.id for d in x.devices()),
                            list(x.shape)])
        return x

    def recorded_setup(seed, stream, n, length, mesh):
        x = sharded_walks(seed, stream, n, length, mesh)
        out["setup"].append(sorted(
            [sh.index[0].start, sh.device.id, list(sh.data.shape)]
            for sh in x.addressable_shards))
        keys = jax.ShapeDtypeStruct((4, 2), jnp.uint32,
                                    sharding=x.sharding)
        out["walk_bytes"] = data._sharded_walk_fn(
            n // 4, length, mesh).lower(keys).compile().memory_analysis(
            ).output_size_in_bytes
        return x

    data.walk_block, data.sharded_walks = recorded, recorded_setup

    def run(name):
        cell = harness.load_cell(name, root=root)
        return harness.run_cell(cell, SEED, 0.5, False, time.monotonic(),
                                require_tpu=False, root=root)

    out["sound"] = run("tiny-rw64-4shard.batch")
    data.walk_block, data.sharded_walks = walk_block, sharded_walks

    cell = harness.load_cell("tiny-rw64-4shard.batch", root=root)
    x = harness.make_series(cell, SEED, harness.mesh_of(cell))
    parts = dict(harness.series(cell, SEED))
    out["placement"] = sorted(
        [sh.index[0].start, sh.device.id, list(sh.data.shape),
         bool(np.array_equal(np.asarray(sh.data),
                             np.asarray(parts[sh.index[0].start]())))]
        for sh in x.addressable_shards)
    del x

    search = FreshIndex.search
    rows = cell.config["n_series"] // cell.shards

    def altered_in_shard_3(self, queries, k=1, **kw):
        d, i = search(self, queries, k=k, **kw)
        i = np.array(i)
        flat = i.reshape(-1)
        j = np.flatnonzero(flat >= 3 * rows)[:1]
        flat[j] = 3 * rows + (flat[j] + 1) % rows
        return d, jnp.asarray(i)

    FreshIndex.search = altered_in_shard_3
    out["fault"] = run("tiny-rw64-4shard.batch")
    FreshIndex.search = search

    out["control"] = control.main(
        ["--workload", "tiny-rw64-4shard.batch", "--seeds", "1", "2", "3",
         "--seconds", "0.3"], root=root, require_tpu=False)
    out["sweep"] = sweep.main(
        ["--workload", "tiny-rw64-4shard.serve", "--rates", "20", "40",
         "--seeds", "5", "6", "--seconds", "0.5"], root=root,
        require_tpu=False)
    out["walk_programs"] = data._sharded_walk_fn.cache_info().currsize
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    root = make_tiny_root(tmp_path_factory.mktemp("tiny4"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={SHARDS}",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    p = subprocess.run([sys.executable, "-c", SCRIPT, root], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}"
    return json.loads(p.stdout.strip().splitlines()[-1]), root


def test_sound_run_is_correct(runs):
    out = runs[0]["sound"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert out["device"]["count"] == SHARDS
    assert set(out["metrics"]) == {"queries_per_s", "setup_s"}


def test_each_device_holds_its_block_and_the_reference_one_at_a_time(runs):
    out = runs[0]
    # set-up made block s on device s, by one program for the mesh whose
    # output is one block a device; the reference made each block on the
    # default device, each gone before the next was made
    blocks = [[s * ROWS, s, [ROWS, 64]] for s in range(SHARDS)]
    assert out["setup"] == [blocks]
    assert out["walk_bytes"] == ROWS * 64 * 4
    assert out["walk_programs"] == 1
    ref = out["made"]
    assert ref and all(m[1:] == [[0], [ROWS, 64]] for m in ref)
    assert sorted({m[0] for m in ref}) == list(range(SHARDS))
    assert out["overlap"] is False
    # device s holds rows s * ROWS onwards, as the reference makes them
    assert out["placement"] == [[s * ROWS, s, [ROWS, 64], True]
                                for s in range(SHARDS)]


def test_an_id_altered_in_the_last_shard_is_not_correct(runs):
    out = runs[0]["fault"]
    assert out["correct"] is False and out["failed"] > 0
    assert out["checks"]["dist_err"]["value"] > out["checks"]["dist_err"][
        "limit"]


def test_control_fails_where_the_program_passes(runs):
    from bench import harness
    out, root = runs
    limits = harness.load_cell("tiny-rw64-4shard.batch",
                               root=root).config["checks"]
    assert all(out["control"][n]["program_max"] <= limits[n] for n in limits)
    assert any(out["control"][n]["control_min"] > limits[n] for n in limits)


def test_sweep_runs_on_the_sharded_serve_cell(runs):
    out = runs[0]["sweep"]
    assert [r["rate"] for r in out] == [20.0, 40.0]
    assert all(r["windows"] == 2 for r in out)
