"""Refine-kernel autotune + roofline bench (`benchmarks/run.py
--autotune-quick`).

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
* ``kernels/refine/roofline_frac``     — one fused refine round timed
  directly through `ops.refine_topk` and divided into the analytic
  roofline bound (`launch.roofline.roofline_fraction`): the
  "fast as the hardware allows" regression number.  On CPU the kernel
  interprets, so the fraction is a tiny correctness-trace value —
  smoke.sh gates it as present and > 0; on real accelerators the same
  row becomes a meaningful %-of-peak.
"""

from __future__ import annotations

import os
import time
from typing import List

import numpy as np

from repro.api import FreshIndex, IndexConfig
from repro.data.synthetic import query_workload, random_walk
from repro.kernels.autotune import device_kind
from repro.launch.roofline import device_peaks, roofline_fraction

from .common import row

RESULTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "results")

N_SERIES = 4_096
SERIES_LEN = 128
LEAF_CAPACITY = 16
N_QUERIES = 32
REPEAT = 5
QUICK = False

# the directly-timed roofline round (kernel-level, no PQ/round loop)
ROOF_Q, ROOF_K, ROOF_ROUNDS = 32, 8, 20


def set_quick() -> None:
    """CI smoke scale: smaller index + two-point autotune grids.  The
    rows' claims (table written, winner bit-exact, roofline_frac > 0)
    are scale-independent; only the timings shrink."""
    global N_SERIES, N_QUERIES, REPEAT, QUICK, ROOF_ROUNDS
    N_SERIES = 2_048
    N_QUERIES = 16
    REPEAT = 3
    QUICK = True
    ROOF_ROUNDS = 10


def _roofline_row() -> dict:
    """Time ONE fused refine round through ops.refine_topk and report
    the achieved fraction of the analytic roofline bound."""
    import jax.numpy as jnp

    from repro.kernels import ops

    k = 10
    M, L = LEAF_CAPACITY, SERIES_LEN
    n_leaves = max(ROOF_K, N_SERIES // M)
    rng = np.random.default_rng(7)
    series = jnp.asarray(rng.standard_normal((n_leaves * M, L)),
                         jnp.float32)
    sq_norms = jnp.sum(series * series, axis=-1).reshape(n_leaves, M)
    q = jnp.asarray(rng.standard_normal((ROOF_Q, L)), jnp.float32)
    q_sq = jnp.sum(q * q, axis=-1)
    ids = jnp.asarray(
        rng.integers(0, n_leaves, (ROOF_Q, ROOF_K)), jnp.int32)
    alive = jnp.ones((ROOF_Q, ROOF_K), jnp.bool_)
    bsf_d = jnp.full((ROOF_Q, k), 3.4e38, jnp.float32)
    bsf_e = jnp.zeros((ROOF_Q, k), jnp.int32)

    def run():
        return ops.refine_topk(q, q_sq, series, sq_norms, ids, alive,
                               bsf_d, bsf_e, leaf_capacity=M, k=k)

    d, _ = run()
    d.block_until_ready()                       # compile outside the clock
    t0 = time.perf_counter()
    for _ in range(ROOF_ROUNDS):
        d, _ = run()
    d.block_until_ready()
    per_round = (time.perf_counter() - t0) / ROOF_ROUNDS

    frac = roofline_fraction(per_round, Q=ROOF_Q, K=ROOF_K, M=M, L=L, k=k)
    peak_flops, hbm_bw = device_peaks()
    return row("kernels/refine/roofline_frac", per_round,
               derived=(f"Q={ROOF_Q} K={ROOF_K} M={M} L={L} "
                        f"device={device_kind()} "
                        f"peaks={peak_flops:.0e}F/{hbm_bw:.0e}B"),
               roofline_frac=float(f"{frac:.4g}"))


def kernels_refine_autotune() -> List[dict]:
    """The autotune sweep + table write + roofline fraction, as rows."""
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
        _roofline_row(),
    ]
    return rows


ALL = [kernels_refine_autotune]
