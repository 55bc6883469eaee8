"""Offline calibration of approximate-search stop rules.

`calibrate(index, ...)` sweeps a grid of `StopRule(eps, max_leaves)`
settings against the tombstone-masked brute-force oracle on a held-out
query sample and, for every (k, recall_target) pair, fits the
smallest-cost setting whose MEASURED recall@k meets the target.  The
result is a `CalibrationTable` keyed by (index fingerprint, k, target)
that

* `FreshIndex.search(q, k, mode="approx", recall_target=...)` resolves
  per call,
* `EngineConfig.latency_tiers` resolves per priority class at serve
  time, and
* `FreshIndex.save` persists next to the checkpoint arrays (in the
  manifest's `extra["quality_calibration"]`) so `FreshIndex.load`
  restores it — calibrate once, serve forever (until the index content
  changes enough that `index.is_calibration_fresh()` goes False).

Cost ordering: among settings that meet the target, the fitter prefers
the fewest mean visited leaves (the device-independent cost model —
wall-clock on the calibration host also gets recorded, but visited
leaves is what transfers across backends), tie-broken by measured
latency.  When NO setting meets the target the exact rule is stored
with `met=False`, so an impossible target degrades to exact search
instead of silently under-delivering recall.
"""

from __future__ import annotations

import dataclasses
import hashlib
import time
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from .stop_rules import EXACT, StopRule

__all__ = ["CalibrationEntry", "CalibrationTable", "calibrate",
           "holdout_queries", "index_fingerprint", "oracle_topk",
           "pq_leaf_candidates", "recall_at_k"]

_BIG = 1e30          # matches core.search.BIG / maintenance DEAD_NORM


# --------------------------------------------------------------------- #
# fingerprint: which index content a table's measured recall refers to
# --------------------------------------------------------------------- #
def index_fingerprint(index) -> str:
    """Stable hex digest of the SEARCHED content of `index`: config,
    core entry norms (which encode membership AND core tombstones),
    pending delta bytes, delta tombstones, and the id high-water mark.
    Two indexes with equal fingerprints answer every query identically,
    so a calibration table measured on one advertises honestly on the
    other."""
    h = hashlib.blake2b(digest_size=16)
    h.update(repr(sorted(index.config.to_dict().items())).encode())
    core = index.index
    h.update(np.asarray(core.sq_norms, np.float32).tobytes())
    h.update(np.asarray(core.perm, np.int32).tobytes())
    for b in index._delta:
        h.update(np.ascontiguousarray(b, np.float32).tobytes())
    h.update(repr(sorted(index._tombstones)).encode())
    h.update(str(index._next_id).encode())
    return h.hexdigest()


# --------------------------------------------------------------------- #
# oracle: tombstone-masked brute force over the live search view
# --------------------------------------------------------------------- #
def _znorm_np(x: np.ndarray) -> np.ndarray:
    mu = x.mean(axis=-1, keepdims=True)
    sd = x.std(axis=-1, keepdims=True)
    return np.where(sd > 1e-8, (x - mu) / np.where(sd > 1e-8, sd, 1.0), 0.0)


def oracle_topk(index, queries, k: int
                ) -> Tuple[np.ndarray, np.ndarray]:
    """(Q, k) ground truth over `index`'s CURRENT search view: exact
    scan of the core arrays (already normalized at build time; dead rows
    carry the sentinel norm and never win) plus the raw pending delta,
    with stable ids (update() aliases applied).  Distances are direct
    form + sqrt, matching `FreshIndex.search` semantics bit-for-bit up
    to summation order.  Host-side numpy on purpose: the oracle must
    not share code with the plan under test."""
    core, delta, alive, id0 = index.search_view()
    znorm = index.config.znorm
    q = np.asarray(queries, np.float32)
    if q.ndim == 1:
        q = q[None]
    qn = _znorm_np(q).astype(np.float32) if znorm else q

    x = np.asarray(core.series, np.float32)          # stored = normalized
    norms = np.asarray(core.sq_norms, np.float32)
    valid = np.asarray(core.valid, bool)
    ids = np.asarray(core.perm, np.int32)
    live = valid & (norms < _BIG / 2)
    cand_x = [x[live]]
    cand_i = [ids[live]]
    if delta is not None:
        dx = np.asarray(delta, np.float32)
        dxn = _znorm_np(dx).astype(np.float32) if znorm else dx
        da = (np.ones(dx.shape[0], bool) if alive is None
              else np.asarray(alive, bool))
        cand_x.append(dxn[da])
        cand_i.append((id0 + np.arange(dx.shape[0], dtype=np.int32))[da])
    X = np.concatenate(cand_x, axis=0)
    I = np.concatenate(cand_i, axis=0)

    d2 = (np.sum(qn * qn, -1)[:, None] + np.sum(X * X, -1)[None, :]
          - 2.0 * qn @ X.T)
    np.maximum(d2, 0.0, out=d2)
    kk = min(k, X.shape[0])
    part = np.argpartition(d2, kk - 1, axis=1)[:, :kk]
    # recompute winners in direct form (the facade's reported metric)
    dd = np.sum(np.square(qn[:, None, :] - X[part]), axis=-1)
    order = np.argsort(dd, axis=1, kind="stable")
    d = np.sqrt(np.take_along_axis(dd, order, axis=1))
    i = I[np.take_along_axis(part, order, axis=1)]
    if kk < k:                                        # pad like the plans
        d = np.pad(d, ((0, 0), (0, k - kk)), constant_values=_BIG)
        i = np.pad(i, ((0, 0), (0, k - kk)), constant_values=-1)
    return d.astype(np.float32), index._remap_ids(i.astype(np.int32))


def pq_leaf_candidates(index, queries, n_leaves: int) -> np.ndarray:
    """(Q, n_leaves * leaf_capacity) stable ids of every series living
    in each query's `n_leaves` best leaves BY LOWER BOUND — the
    candidate universe an approx plan capped at `max_leaves=n_leaves`
    can ever return from the core (-1 marks invalid slots).  Pending
    delta rows are always additionally reachable (the delta scan stays
    exact) — callers union them in.  Used by the containment invariant
    test: approx results ⊆ these candidates ∪ delta ids."""
    import jax
    import jax.numpy as jnp
    from repro.core.search import leaf_lower_bounds, prepare_queries

    core, _, _, _ = index.search_view()
    q = jnp.asarray(np.atleast_2d(np.asarray(queries, np.float32)))
    qz, q_paa = prepare_queries(q, index.config.znorm, index=core)
    lb = leaf_lower_bounds(core, q_paa, core.series.shape[1],
                           index.config.backend)
    n = min(n_leaves, core.n_leaves)
    _, leaf_order = jax.lax.top_k(-lb, n)             # (Q, n) best leaves
    leaf_order = np.asarray(leaf_order)
    M = core.leaf_capacity
    ids = np.asarray(core.perm, np.int32).reshape(core.n_leaves, M)
    valid = np.asarray(core.valid, bool).reshape(core.n_leaves, M)
    norms = np.asarray(core.sq_norms, np.float32).reshape(core.n_leaves, M)
    members = np.where(valid & (norms < _BIG / 2), ids, -1)
    out = members[leaf_order].reshape(leaf_order.shape[0], -1)
    alias = out >= 0
    out[alias] = index._remap_ids(out[alias])
    return out


def recall_at_k(result_ids: np.ndarray, oracle_ids: np.ndarray) -> float:
    """Mean fraction of each row's oracle ids present in the result row
    (-1 slots on either side never count as matches)."""
    r = np.atleast_2d(np.asarray(result_ids))
    o = np.atleast_2d(np.asarray(oracle_ids))
    hits = 0
    total = 0
    for rr, oo in zip(r, o):
        truth = set(int(v) for v in oo if v >= 0)
        if not truth:
            continue
        got = set(int(v) for v in rr if v >= 0)
        hits += len(truth & got)
        total += len(truth)
    return hits / total if total else 1.0


def holdout_queries(index, n: int = 64, noise: float = 0.25,
                    seed: int = 0) -> np.ndarray:
    """Synthesize an (n, L) held-out query sample: live indexed series
    perturbed with `noise` * per-row-std Gaussian jitter — near-duplicate
    workload, the regime approximate search serves.  Deterministic in
    `seed`; callers wanting a different workload pass their own queries
    to `calibrate` instead."""
    rng = np.random.default_rng(seed)
    core, delta, alive, _ = index.search_view()
    x = np.asarray(core.series, np.float32)
    live = (np.asarray(core.valid, bool)
            & (np.asarray(core.sq_norms, np.float32) < _BIG / 2))
    rows = [x[live]]
    if delta is not None:
        dx = np.asarray(delta, np.float32)
        da = (np.ones(dx.shape[0], bool) if alive is None
              else np.asarray(alive, bool))
        rows.append(dx[da])
    pool = np.concatenate(rows, axis=0)
    if pool.shape[0] == 0:
        raise ValueError("cannot synthesize holdout queries from an "
                         "index with no live series")
    base = pool[rng.integers(0, pool.shape[0], size=n)]
    sd = base.std(axis=-1, keepdims=True)
    sd = np.where(sd > 1e-8, sd, 1.0)
    return (base + noise * sd * rng.standard_normal(base.shape)
            ).astype(np.float32)


# --------------------------------------------------------------------- #
# the table
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class CalibrationEntry:
    """One fitted setting: the rule plus the evidence behind it —
    measured recall on the holdout, mean visited-leaf fraction,
    measured per-batch latency on the calibration host, and whether the
    target was actually met (False = the exact fallback was stored)."""
    rule: StopRule
    recall: float
    visited_frac: float
    latency_us: float
    met: bool = True

    def to_dict(self) -> dict:
        return {"rule": self.rule.to_dict(), "recall": self.recall,
                "visited_frac": self.visited_frac,
                "latency_us": self.latency_us, "met": self.met}

    @classmethod
    def from_dict(cls, d: dict) -> "CalibrationEntry":
        return cls(rule=StopRule.from_dict(d["rule"]),
                   recall=float(d["recall"]),
                   visited_frac=float(d["visited_frac"]),
                   latency_us=float(d["latency_us"]),
                   met=bool(d.get("met", True)))


class CalibrationTable:
    """(k, recall_target) -> CalibrationEntry, plus the fingerprint of
    the index content the measurements were taken on.  Targets are
    keyed at 6-decimal precision so float round-trips through JSON can
    never miss a lookup."""

    def __init__(self, fingerprint: str,
                 entries: Optional[Dict[Tuple[int, float],
                                        CalibrationEntry]] = None):
        self.fingerprint = fingerprint
        self._entries: Dict[Tuple[int, float], CalibrationEntry] = \
            dict(entries or {})

    @staticmethod
    def _key(k: int, target: float) -> Tuple[int, float]:
        return (int(k), round(float(target), 6))

    def put(self, k: int, target: float, entry: CalibrationEntry) -> None:
        """Insert/replace the fitted entry for (k, target)."""
        self._entries[self._key(k, target)] = entry

    def lookup(self, k: int, target: float) -> Optional[CalibrationEntry]:
        """The fitted entry for (k, target), None when never calibrated."""
        return self._entries.get(self._key(k, target))

    def __len__(self) -> int:
        return len(self._entries)

    def items(self):
        """Iterate ((k, target), entry) pairs, sorted for stable output."""
        return sorted(self._entries.items())

    def to_dict(self) -> dict:
        """JSON-ready form (checkpoint `extra` payload)."""
        return {"fingerprint": self.fingerprint,
                "entries": [{"k": k, "target": t, **e.to_dict()}
                            for (k, t), e in self.items()]}

    @classmethod
    def from_dict(cls, d: dict) -> "CalibrationTable":
        """Inverse of `to_dict`."""
        t = cls(d["fingerprint"])
        for e in d.get("entries", ()):
            t.put(int(e["k"]), float(e["target"]),
                  CalibrationEntry.from_dict(e))
        return t

    def __repr__(self) -> str:
        return (f"CalibrationTable(entries={len(self._entries)}, "
                f"fingerprint={self.fingerprint[:8]}...)")


# --------------------------------------------------------------------- #
# the calibrator
# --------------------------------------------------------------------- #
def _default_leaves_grid(n_leaves: int, round_leaves: int
                         ) -> Tuple[int, ...]:
    """Power-of-two visited-leaf caps from one round up to half the
    tree — the frontier sweep never needs the uncapped end because the
    eps=0,uncapped point IS exact search."""
    out = []
    b = max(1, round_leaves)
    while b < n_leaves:
        out.append(b)
        b *= 2
    return tuple(out) or (max(1, n_leaves // 2),)


def _run_setting(index, q, k: int, rule: StopRule, backend: Optional[str],
                 repeat: int) -> Tuple[np.ndarray, int, float]:
    """Execute one (rule, k) setting over the holdout through the SAME
    jitted plans serving uses.  Returns (stable ids (Q, k), visited
    leaves, median latency seconds)."""
    import jax.numpy as jnp
    from repro.core.search import search_plan, snapshot_search

    core, delta, alive, id0 = index.search_view()
    cfg = index.config
    bk = backend if backend is not None else cfg.backend
    # the fully-resolved knobs serving will use (IndexConfig > fresh
    # autotune table > defaults) — calibration must measure the same
    # program it certifies
    kn = index.search_knobs()
    K = kn.round_leaves
    dd = kn.dma_depth if bk == "pallas" else 1
    kw = dict(k=k, round_leaves=K, znorm=cfg.znorm, backend=bk,
              pq_budget=kn.pq_budget, dma_depth=dd,
              **rule.lower())
    qj = jnp.asarray(q)

    def run():
        if delta is None:
            return search_plan(core, qj, **kw)
        return snapshot_search(core, delta, qj, alive, n_base=id0, **kw)

    d, i, counts = run()                    # warmup (compile) + answers
    d.block_until_ready()
    ts = []
    for _ in range(max(1, repeat)):
        t0 = time.perf_counter()
        out = run()
        out[0].block_until_ready()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    budget = core.n_leaves
    for cap in (kn.pq_budget, rule.max_leaves):
        if cap is not None:
            budget = min(budget, cap)
    visited = min(int(counts[0]) * K, budget)       # counts[0]: rounds
    return (index._remap_ids(np.asarray(i, np.int32)), visited,
            ts[len(ts) // 2])


def calibrate(index, *, ks: Sequence[int] = (1, 5, 10),
              targets: Sequence[float] = (0.95,),
              queries=None, n_queries: int = 64, noise: float = 0.25,
              seed: int = 0,
              eps_grid: Sequence[float] = (0.0, 0.05, 0.1, 0.25, 0.5),
              leaves_grid: Optional[Sequence[int]] = None,
              backend: Optional[str] = None,
              repeat: int = 3) -> CalibrationTable:
    """Fit stop rules for every (k in `ks`, target in `targets`) pair.

    Sweeps the (eps_grid x leaves_grid) cross product on a held-out
    sample (`queries`, or `n_queries` synthesized near-duplicates, see
    `holdout_queries`), measures recall@k against `oracle_topk`, and
    stores the cheapest setting meeting each target (see module
    docstring for the cost ordering).  Every setting executes through
    the same jitted plans serving dispatches, so visited-leaf counts
    and latencies are the real thing, not a model.

    Returns the fitted `CalibrationTable`; callers normally invoke this
    via `FreshIndex.calibrate(...)`, which also installs the table on
    the index so search/serving/persistence pick it up.
    """
    for t in targets:
        if not 0.0 < t <= 1.0:
            raise ValueError(f"recall targets must be in (0, 1], got {t}")
    q = (np.asarray(queries, np.float32) if queries is not None
         else holdout_queries(index, n_queries, noise, seed))
    if q.ndim == 1:
        q = q[None]
    core, _, _, _ = index.search_view()
    n_leaves = core.n_leaves
    grid_leaves = (tuple(leaves_grid) if leaves_grid is not None
                   else _default_leaves_grid(
                       n_leaves, index.search_knobs().round_leaves))
    settings = [StopRule(eps=e, max_leaves=m)
                for m in grid_leaves for e in eps_grid]

    table = CalibrationTable(index_fingerprint(index))
    measured = []                           # (rule, k) -> evidence rows
    oracles = {}
    for k in ks:
        k = int(k)
        if k > index.n_series:
            raise ValueError(f"calibration k={k} exceeds the "
                             f"{index.n_series} live series")
        _, oracle_ids = oracle_topk(index, q, k)
        oracles[k] = oracle_ids
        for rule in settings:
            ids, visited, lat = _run_setting(index, q, k, rule, backend,
                                             repeat)
            measured.append((k, rule, recall_at_k(ids, oracle_ids),
                             visited / max(1, n_leaves), lat * 1e6))
        # the exact reference point (for `met=False` fallbacks and so
        # the frontier always contains a recall=1.0 anchor)
        ids, visited, lat = _run_setting(index, q, k, EXACT, backend,
                                         repeat)
        measured.append((k, EXACT, recall_at_k(ids, oracles[k]),
                         visited / max(1, n_leaves), lat * 1e6))

    for k in (int(k) for k in ks):
        rows = [m for m in measured if m[0] == k]
        for target in targets:
            ok = [m for m in rows if m[2] >= target]
            if ok:
                _, rule, rec, vf, lat = min(
                    ok, key=lambda m: (m[3], m[4]))
                table.put(k, target, CalibrationEntry(
                    rule=rule, recall=rec, visited_frac=vf,
                    latency_us=lat, met=True))
            else:                           # degrade to exact, loudly
                exact = next(m for m in rows if m[1].is_exact)
                table.put(k, target, CalibrationEntry(
                    rule=EXACT, recall=exact[2], visited_frac=exact[3],
                    latency_us=exact[4], met=False))
    return table
