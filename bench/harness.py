"""One cell of the benchmark, from its entry in BENCHMARK.json to the
result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name the cell gives:

  bench/configs/<config>.json   the deployment: sizes, IndexConfig
                                fields, "shards" (1 where absent), source,
                                the limits of the check
  bench/traffic/<traffic>.json  the mix's parameters; its "loop" names
  bench/loops/<loop>.py         the generator and window loop that
                                drives the entry point
  bench/metrics/<metric>.py     read(reading) -> a number, or None where
                                the run holds nothing to read

A run: the chip is checked first; then set-up (JAX, the series from the
seed on the device, the build, the loop's warm-up of its own shapes),
then the measured window, then the device's peak memory, then the
program's state is freed and the plain reference (`reference.py`)
checks every answer the window produced.

A configuration with "shards": S > 1 is a deployment whose series are
S row blocks of n_series / S, one block on each of the cell's chips
(`data.sharded_walks`).  The program builds its index over that placed
array through `FreshIndex.build` and is then placed over the mesh by
`FreshIndex.shard`.  The reference makes the blocks again, one at a
time on one device, as the parts of `series`.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from bench import tracing

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


# --------------------------------------------------------------------- #
# finding a cell's files
# --------------------------------------------------------------------- #
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def shards(self) -> int:
        """Row blocks of the series, one a chip."""
        return int(self.config.get("shards", 1))


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(bench_dir: str, kind: str, name: str):
    """The module in `<bench_dir>/<kind>/<name>.py`, loaded from its
    path (names hold dots, so they are not importable by name)."""
    path = os.path.join(bench_dir, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: str = ROOT,
              bench_dir: Optional[str] = None) -> Cell:
    """The cell `name` of `<root>/BENCHMARK.json`, with its configuration
    and mix read from their files under `bench_dir`."""
    bench_dir = bench_dir or os.path.join(root, "bench")
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in spec['workloads']]}")

    def mine(m):
        return name in m.get("workloads", [name])
    e2e = [m for m in spec["end_to_end"] if mine(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    cell = Cell(
        name=name, chips=int(entry["chips"]),
        config_name=entry["config"],
        config=_json(os.path.join(bench_dir, "configs",
                                  entry["config"] + ".json")),
        traffic_name=entry["traffic"],
        mix=_json(os.path.join(bench_dir, "traffic",
                               entry["traffic"] + ".json")),
        end_to_end=e2e, per_layer=per_layer)
    if cell.shards != cell.chips:
        raise ValueError(f"{name}: configuration {cell.config_name!r} has "
                         f"{cell.shards} shards, the cell {cell.chips} chips")
    if int(cell.config["n_series"]) % cell.shards:
        raise ValueError(f"{cell.config_name}: {cell.config['n_series']} "
                         f"series do not split into {cell.shards} shards")
    return cell


# --------------------------------------------------------------------- #
# what a loop and a metric reader see
# --------------------------------------------------------------------- #
@dataclasses.dataclass
class Window:
    """What a loop's `measure` returns."""
    seconds: float                       # the window's length
    attempted: int                       # queries sent
    errors: int                          # queries that raised or timed out
    metrics: Dict[str, float]            # end-to-end metrics by name
    queries: np.ndarray                  # (n, L) host: every query answered
    dist: np.ndarray                     # (n, k) host: its answer
    ids: np.ndarray                      # (n, k)
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Run:
    """A run's state, handed to the loop."""
    cell: Cell
    seed: int
    index: Any                           # the FreshIndex under test
    series_len: int
    state: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's `read` sees after a traced window."""
    cell: Cell
    window: Window
    trace: Any                           # tracing.Trace
    shapes: Dict[str, int]               # n_series, series_len, n_leaves,
                                         # segments, leaf_capacity
    device_kind: str
    bench_dir: str

    def peaks(self) -> dict:
        """This chip's row of peaks.json (KeyError where it has none)."""
        return peaks_for(self.device_kind, self.bench_dir)


# --------------------------------------------------------------------- #
# the chip, the compile cache, compile counting
# --------------------------------------------------------------------- #
class NoChip(RuntimeError):
    pass


def chips_or_fail(want: int):
    """The first `want` TPU devices; NoChip where JAX finds none or too
    few."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < want:
        raise NoChip(f"the cell needs {want} chips, JAX found {len(devs)}")
    return devs[:want]


def use_compile_cache(root: str) -> str:
    """JAX's persistent cache at the fixed path `<checkout>/.jax_cache`
    (whatever `$JAX_COMPILATION_CACHE_DIR` says), holding every program
    however small or quick to compile, so that a cell's second run in a
    checkout compiles nothing and two checkouts share nothing."""
    import jax
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts backend compiles, their seconds, and persistent cache hits
    and misses, from JAX's monitoring events (every thread)."""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compiles += 1
                self.compile_s += duration

    def _on_event(self, event: str, **_) -> None:
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# --------------------------------------------------------------------- #
# a run
# --------------------------------------------------------------------- #
def check(parts, window: Window, k: int, limits: Dict[str, float]):
    """Compare every answer of the window with the plain reference over
    the series' `parts` (`series`).  Returns (checks, wrong, unsure):
    each number with its limit, the answers beyond a limit, and the
    queries the reference could not certify."""
    from bench import reference
    ref = reference.Reference(parts, window.queries, k)
    dist_err, rank_gap = reference.compare(parts, window.queries, ref,
                                           window.dist, window.ids)
    wrong = int(((dist_err > limits["dist_err"])
                 | (rank_gap > limits["rank_gap"])).sum())
    checks = {
        "dist_err": {"value": float(dist_err.max()),
                     "limit": limits["dist_err"]},
        "rank_gap": {"value": float(rank_gap.max()),
                     "limit": limits["rank_gap"]},
    }
    return checks, wrong, ref.unsure


def mesh_of(cell: Cell):
    """The cell's mesh over its first `shards` devices, axis "data";
    None for one shard."""
    if cell.shards == 1:
        return None
    import jax
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:cell.shards]), ("data",))


def make_series(cell: Cell, seed: int, mesh=None):
    """The cell's (n_series, series_len) series from the seed: on the
    default device, or row-sharded over `mesh` with each block made on
    its own device."""
    from bench import data
    n, L = int(cell.config["n_series"]), int(cell.config["series_len"])
    if mesh is None:
        return data.walks(seed, data.DATA, n, L)
    return data.sharded_walks(seed, data.DATA, n, L, mesh)


def set_up(cell: Cell, seed: int, seconds: float, bench_dir: str):
    """The series from the seed, the index built over them (and placed
    over the mesh where the configuration has shards), and the loop's
    warm-up: (run, loop, shapes, seconds of each phase)."""
    from repro.api import FreshIndex, IndexConfig
    cfg = cell.config
    L = int(cfg["series_len"])
    loop = load_module(bench_dir, "loops", cell.mix["loop"])
    mesh = mesh_of(cell)
    phases = {}
    t = time.perf_counter()
    raw = make_series(cell, seed, mesh)
    raw.block_until_ready()
    phases["data_s"] = time.perf_counter() - t
    t = time.perf_counter()
    index = FreshIndex.build(raw, IndexConfig(**cfg["index"]))
    index.index.series.block_until_ready()
    phases["build_s"] = time.perf_counter() - t
    del raw
    if mesh is not None:
        t = time.perf_counter()
        index.shard(mesh)
        index.index.series.block_until_ready()
        phases["shard_s"] = time.perf_counter() - t
    shapes = {"n_series": int(cfg["n_series"]), "series_len": L,
              "n_leaves": int(index.index.n_leaves),
              "leaf_capacity": int(index.index.leaf_capacity),
              "segments": int(index.index.paa.shape[1])}
    run = Run(cell=cell, seed=seed, index=index, series_len=L)
    t = time.perf_counter()
    loop.prepare(run, seconds)
    phases["warmup_s"] = time.perf_counter() - t
    return run, loop, shapes, phases


def tear_down(run: Run, loop) -> None:
    """Free the program's state, so the reference has the chip."""
    loop.close(run)
    run.index = None
    gc.collect()


def series(cell: Cell, seed: int) -> list:
    """The cell's series made again from the seed for the reference, as
    parts `(first_row, make)` (see `reference`), each made on the
    default device when the reference visits it: the whole array for
    one shard, block s of S shards for part s."""
    import functools

    from bench import data
    n, L = int(cell.config["n_series"]), int(cell.config["series_len"])
    if cell.shards == 1:
        return [(0, functools.partial(data.walks, seed, data.DATA, n, L))]
    rows = n // cell.shards
    return [(s * rows, functools.partial(data.walk_block, seed, data.DATA,
                                         s, rows, L))
            for s in range(cell.shards)]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, require_tpu: bool = True,
             root: str = ROOT, bench_dir: Optional[str] = None,
             keep_trace: Optional[str] = None) -> dict:
    """Set up, measure and check one run of `cell`; the result line as
    a dict.  Raises NoChip before any work where the chip is missing."""
    import jax
    bench_dir = bench_dir or os.path.join(root, "bench")
    if require_tpu:
        devs = chips_or_fail(cell.chips)
    else:
        devs = jax.devices()[:cell.chips]
    dev = devs[0]
    cache_dir = use_compile_cache(root)
    counter = CompileCounter()

    run, loop, shapes, phases = set_up(cell, seed, seconds, bench_dir)
    setup_s = time.monotonic() - t_start
    before = (counter.compiles, counter.compile_s)
    log(f"bench: set-up {setup_s:.3f} s ("
        + ", ".join(f"{k} {v:.3f}" for k, v in phases.items())
        + f"); compiles {counter.compiles} ({counter.compile_s:.3f} s), "
        f"cache {cache_dir}: {counter.hits} hits, {counter.misses} misses")

    tmp = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        if trace:
            jax.profiler.start_trace(tmp)
        try:
            with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
                window = loop.measure(run, seconds)
        finally:
            t = time.perf_counter()
            if trace:
                jax.profiler.stop_trace()
            stop_s = time.perf_counter() - t
        log(f"bench: window {window.seconds:.3f} s, {window.attempted} "
            f"queries, {window.errors} errors, "
            f"{counter.compiles - before[0]} compiles inside "
            f"({counter.compile_s - before[1]:.3f} s); {window.notes}"
            + (f"; trace written in {stop_s:.3f} s" if trace else ""))
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs)
        tear_down(run, loop)

        metrics: Dict[str, Any] = {}
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devs), "memory_peak_bytes": int(peak)}
        result: Dict[str, Any] = {}
        if trace:
            t = time.perf_counter()
            tr = tracing.read_xplane(tmp)
            if keep_trace:
                tracing.dump(tr, keep_trace)
            device["busy_s"] = tracing.busy_s(tr)
            device["window_s"] = tr.window_s
            reading = Reading(cell=cell, window=window, trace=tr,
                              shapes=shapes, device_kind=dev.device_kind,
                              bench_dir=bench_dir)
            for m in cell.per_layer:
                v = load_module(bench_dir, "metrics", m["name"]).read(reading)
                if v is not None:
                    metrics[m["name"]] = {"value": float(v),
                                          "unit": m["unit"]}
            result["breakdown"] = tracing.breakdown(tr)
            log(f"bench: trace of {sum(map(len, tr.ops.values()))} device "
                f"and {len(tr.host)} host events read and reduced in "
                f"{time.perf_counter() - t:.3f} s")
        else:
            for m in cell.end_to_end:
                v = (setup_s if m["name"] == "setup_s"
                     else window.metrics[m["name"]])
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)

    t = time.perf_counter()
    checks, wrong, unsure = check(series(cell, seed), window,
                                  int(cell.mix["k"]), cell.config["checks"])
    log(f"bench: reference check of {window.queries.shape[0]} answers "
        f"{time.perf_counter() - t:.3f} s; {unsure} queries the "
        f"reference could not certify")
    failed = window.errors + wrong
    correct = (window.attempted > 0 and failed == 0 and unsure == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    for name, c in checks.items():
        log(f"check {name}: {c['value']:.6g} (limit {c['limit']:.6g})")
    return {"correct": bool(correct), "attempted": int(window.attempted),
            "failed": int(failed), "metrics": metrics, "device": device,
            **result, "checks": checks}


def tool_cell(workload: str, seconds: Optional[float], root: str,
              require_tpu: bool = True):
    """For the scripts beside the benchmark (`control.py`, `sweep.py`):
    the cell and its window length (`run_seconds` where `seconds` is
    None), with the chip checked and the compile cache on."""
    cell = load_cell(workload, root=root)
    if seconds is None:
        seconds = _json(os.path.join(root, "BENCHMARK.json"))["run_seconds"]
    if require_tpu:
        chips_or_fail(cell.chips)
    use_compile_cache(root)
    return cell, seconds


def peaks_for(kind: str, bench_dir: str) -> dict:
    """This chip's published peaks; KeyError for a kind not listed."""
    table = _json(os.path.join(bench_dir, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"listed: {sorted(table)}")
    return table[kind]


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.monotonic() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       t_start)
    except NoChip as e:
        log(f"bench: {e}; nothing was run")
        return 2
    print(json.dumps(out), flush=True)
    return 0
