"""Refine-kernel autotune bench (`benchmarks/run.py --autotune-quick`).

Emits the backend-tuning rows next to the figure rows in
BENCH_fresh.json:

* ``kernels/refine/autotune/baseline`` — default-knob search latency
  (the untuned reference every tuned number is judged against).
* ``kernels/refine/autotune/winner``   — the sweep winner's latency,
  its TuneConfig, the speedup over baseline, and how many candidates
  survived the bitwise exactness gate (`kernels.autotune` rejects any
  config whose output is not bit-identical to the default's, so the
  speedup is free of semantic drift by construction).
* ``kernels/refine/autotune/table``    — proof of the table write: the
  AutotuneTable is persisted as JSON under results/ and the row records
  its path, entry count and content fingerprint.
"""

from __future__ import annotations

import os
import time
from typing import List

from repro.api import FreshIndex, IndexConfig
from repro.data.synthetic import query_workload, random_walk

from .common import row

RESULTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "results")

N_SERIES = 4_096
SERIES_LEN = 128
LEAF_CAPACITY = 16
N_QUERIES = 32
REPEAT = 5
QUICK = False


def set_quick() -> None:
    """CI smoke scale: smaller index + two-point autotune grids.  The
    rows' claims (table written, winner bit-exact) are
    scale-independent; only the timings shrink."""
    global N_SERIES, N_QUERIES, REPEAT, QUICK
    N_SERIES = 2_048
    N_QUERIES = 16
    REPEAT = 3
    QUICK = True


def kernels_refine_autotune() -> List[dict]:
    """The autotune sweep + table write, as rows."""
    walks = random_walk(N_SERIES, SERIES_LEN, seed=71)
    queries = query_workload(walks, N_QUERIES, noise_sigma=0.05, seed=72)
    ix = FreshIndex.build(
        walks, IndexConfig(leaf_capacity=LEAF_CAPACITY, backend="pallas"))

    t0 = time.perf_counter()
    table = ix.autotune(queries=queries, repeat=REPEAT, quick=QUICK)
    sweep_s = time.perf_counter() - t0
    ((key, entry),) = table.items()
    cfg = entry.config

    os.makedirs(RESULTS, exist_ok=True)
    path = os.path.join(RESULTS, "autotune_table.json")
    table.save_json(path)

    rows = [
        row("kernels/refine/autotune/baseline", entry.baseline_ms * 1e-3,
            derived="default-knob search over the bench batch"),
        row("kernels/refine/autotune/winner", entry.median_ms * 1e-3,
            derived=(f"round_leaves={cfg.round_leaves} "
                     f"dma_depth={cfg.dma_depth} "
                     f"pq_budget={cfg.pq_budget}"),
            speedup=round(entry.baseline_ms
                          / max(entry.median_ms, 1e-9), 3),
            n_exact=entry.n_exact, n_candidates=entry.n_candidates),
        row("kernels/refine/autotune/table", sweep_s,
            derived=(f"entries={len(table)} device={key[0]} "
                     f"fingerprint={table.fingerprint[:12]}"),
            path=os.path.relpath(path, os.path.dirname(RESULTS))),
    ]
    return rows


ALL = [kernels_refine_autotune]
