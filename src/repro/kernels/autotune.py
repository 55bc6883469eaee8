"""Backend autotune: sweep refine-kernel knobs on the live device and
cache the winner next to checkpoints.

The refine kernel's profitable knob settings are hardware facts — the
Mosaic DMA ring depth that hides HBM latency, the `round_leaves` batch
that amortizes one kernel launch — not index semantics, so they do not
belong in code as static defaults.  This module measures them:
`autotune_index` enumerates candidate `TuneConfig`s (`candidate_space`),
times each one through the SAME jitted search plans serving dispatches
(mirroring `quality.calibrate._run_setting`), and stores the fastest in an
`AutotuneTable` keyed by `(device_kind, L, leaf_capacity, dtype)` —
the four facts that determine the kernel's shape.  `FreshIndex` persists
the table with its checkpoint (`extra["autotune"]`) and resolves UNSET
IndexConfig knobs through it (`FreshIndex.search_knobs`); a key miss —
an unknown device, a different series length — falls back to today's
static defaults, so an untuned process behaves exactly as before.

Exactness gate: every candidate must reproduce the default-knob search
output BITWISE on the live device, on BOTH backends ('pallas' and
'ref'), before it may be timed.  The kernel variants guarantee
entries-exact results with distances within ~1-2 ulp (see
`kernels.refine`), and the search plan's direct-form recompute usually
collapses even that — but "usually" is not a contract, so the sweep
proves it per device and rejects any candidate that fails.  Tuned
search being bit-identical to untuned search therefore holds by
construction, which is what lets the serving layer adopt a table
without a recall re-certification.

Staleness: like `quality.CalibrationTable`, the table records the
`index_fingerprint` of the content it was measured on.  Timings are
content-dependent (leaf fill, pruning rates), so `FreshIndex` refuses
to resolve knobs through a stale table (mutations make it stale) — it
falls back to defaults and surfaces `is_autotune_fresh()` so operators
re-tune, exactly the calibration semantics.
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

#: the static defaults every knob falls back to when neither IndexConfig
#: nor a fresh AutotuneTable sets it — today's (pre-autotune) behavior.
DEFAULTS: Dict[str, Optional[int]] = {
    "round_leaves": 8,
    "pq_budget": None,
    "dma_depth": 1,
}


@dataclasses.dataclass(frozen=True)
class TuneConfig:
    """One fully-resolved setting of the sweepable search knobs.

    round_leaves  leaves refined per query per round (both backends)
    pq_budget     PQ admission cap (None = exact full budget); a finite
                  value only survives the sweep's bitwise gate when it
                  provably changes nothing on this index
    dma_depth     Mosaic HBM->VMEM DMA ring depth (pallas only; 1 = the
                  pipelined BlockSpec kernel, >= 2 = the explicit
                  double/multi-buffered ring)
    """
    round_leaves: int = 8
    pq_budget: Optional[int] = None
    dma_depth: int = 1

    def to_dict(self) -> dict:
        """Plain-dict form (JSON / checkpoint payload)."""
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "TuneConfig":
        """Inverse of `to_dict`; unknown keys ignored for forward compat."""
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})


@dataclasses.dataclass(frozen=True)
class TuneEntry:
    """One table row: the winning config plus the evidence behind it —
    its median latency, the default-knob baseline it beat (or tied),
    and how many of the swept candidates survived the bitwise gate."""
    config: TuneConfig
    median_ms: float
    baseline_ms: float
    n_candidates: int
    n_exact: int

    def to_dict(self) -> dict:
        return {"config": self.config.to_dict(),
                "median_ms": self.median_ms,
                "baseline_ms": self.baseline_ms,
                "n_candidates": self.n_candidates,
                "n_exact": self.n_exact}

    @classmethod
    def from_dict(cls, d: dict) -> "TuneEntry":
        return cls(config=TuneConfig.from_dict(d["config"]),
                   median_ms=float(d["median_ms"]),
                   baseline_ms=float(d["baseline_ms"]),
                   n_candidates=int(d["n_candidates"]),
                   n_exact=int(d["n_exact"]))


def device_kind() -> str:
    """The live accelerator's kind string — the table's first key part.

    `jax.devices()[0].device_kind` where available (e.g. 'TPU v5 lite'),
    else the platform name; lookups and stores go
    through this one helper so they can never disagree on spelling.
    """
    import jax
    d = jax.devices()[0]
    return str(getattr(d, "device_kind", None) or jax.default_backend())


class AutotuneTable:
    """(device_kind, L, leaf_capacity, dtype) -> TuneEntry, plus the
    fingerprint of the index content the timings were measured on
    (mirrors `quality.CalibrationTable`)."""

    def __init__(self, fingerprint: str,
                 entries: Optional[Dict[Tuple[str, int, int, str],
                                        TuneEntry]] = None):
        self.fingerprint = fingerprint
        self._entries: Dict[Tuple[str, int, int, str], TuneEntry] = \
            dict(entries or {})

    @staticmethod
    def _key(device: str, L: int, leaf_capacity: int,
             dtype: str) -> Tuple[str, int, int, str]:
        return (str(device), int(L), int(leaf_capacity), str(dtype))

    def put(self, device: str, L: int, leaf_capacity: int, dtype: str,
            entry: TuneEntry) -> None:
        """Insert/replace the winner for one device/shape key."""
        self._entries[self._key(device, L, leaf_capacity, dtype)] = entry

    def lookup(self, device: str, L: int, leaf_capacity: int,
               dtype: str) -> Optional[TuneEntry]:
        """The tuned entry for this key; None (-> static defaults) when
        the device/shape was never swept — the unknown-device fallback."""
        return self._entries.get(self._key(device, L, leaf_capacity, dtype))

    def __len__(self) -> int:
        return len(self._entries)

    def items(self):
        """Iterate (key, entry) pairs, sorted for stable output."""
        return sorted(self._entries.items())

    def to_dict(self) -> dict:
        """JSON-ready form (checkpoint `extra["autotune"]` payload)."""
        return {"fingerprint": self.fingerprint,
                "entries": [{"device": k[0], "L": k[1],
                             "leaf_capacity": k[2], "dtype": k[3],
                             **e.to_dict()}
                            for k, e in self.items()]}

    @classmethod
    def from_dict(cls, d: dict) -> "AutotuneTable":
        """Inverse of `to_dict`."""
        t = cls(d["fingerprint"])
        for e in d.get("entries", ()):
            t.put(e["device"], int(e["L"]), int(e["leaf_capacity"]),
                  e["dtype"], TuneEntry.from_dict(e))
        return t

    def save_json(self, path: str) -> None:
        """Write the table as JSON (the standalone spelling the bench
        harness uses; FreshIndex.save embeds `to_dict` in the
        checkpoint manifest instead)."""
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=2, sort_keys=True)
            f.write("\n")

    @classmethod
    def load_json(cls, path: str) -> "AutotuneTable":
        """Inverse of `save_json`."""
        with open(path) as f:
            return cls.from_dict(json.load(f))

    def __repr__(self) -> str:
        return (f"AutotuneTable(entries={len(self._entries)}, "
                f"fingerprint={self.fingerprint[:8]}...)")


def resolve_knobs(config, entry: Optional[TuneEntry] = None) -> TuneConfig:
    """The one knob-resolution chain: explicit IndexConfig field (not
    None) > fresh tuned entry > static `DEFAULTS`.  `config` may be None
    (pure table/default resolution); callers pass `entry=None` for the
    unknown-device / stale-table fallback and get today's defaults."""
    t = entry.config if entry is not None else None

    def pick(name):
        v = getattr(config, name, None) if config is not None else None
        if v is not None:
            return v
        if t is not None:
            return getattr(t, name)
        return DEFAULTS[name]

    return TuneConfig(round_leaves=pick("round_leaves"),
                      pq_budget=pick("pq_budget"),
                      dma_depth=pick("dma_depth"))


def candidate_space(*, quick: bool = False,
                    round_leaves_grid: Optional[Sequence[int]] = None,
                    pq_budgets: Sequence[Optional[int]] = (None,),
                    dma_depths: Optional[Sequence[int]] = None
                    ) -> Tuple[TuneConfig, ...]:
    """Enumerate the sweep's candidate TuneConfigs: `dma_depths`
    crossed with `round_leaves_grid` and `pq_budgets`.  `quick` shrinks
    every axis to a two-point grid (the CI smoke leg).  The default
    config is always candidate 0, so the sweep can never return an
    empty or all-rejected space.
    """
    if round_leaves_grid is None:
        round_leaves_grid = (8, 16) if quick else (4, 8, 16)
    if dma_depths is None:
        dma_depths = (1, 2) if quick else (1, 2, 4)
    out = [TuneConfig()]
    for rl in round_leaves_grid:
        for pq in pq_budgets:
            for dd in dma_depths:
                out.append(TuneConfig(round_leaves=rl, pq_budget=pq,
                                      dma_depth=dd))
    seen, uniq = set(), []
    for c in out:
        if c not in seen:
            seen.add(c)
            uniq.append(c)
    return tuple(uniq)


def _run_tuned(index, qj, k: int, tc: TuneConfig, backend: str):
    """Execute one (TuneConfig, backend) setting over the query batch
    through the same jitted plans serving uses; returns (dist, ids)
    device arrays."""
    from repro.core.search import search_plan, snapshot_search

    core, delta, alive, id0 = index.search_view()
    dd = tc.dma_depth if backend == "pallas" else 1
    kw = dict(k=k, round_leaves=tc.round_leaves, znorm=index.config.znorm,
              backend=backend, pq_budget=tc.pq_budget, dma_depth=dd)
    if delta is None:
        d, i, _ = search_plan(core, qj, **kw)
    else:
        d, i, _ = snapshot_search(core, delta, qj, alive, n_base=id0, **kw)
    return d, i


def _time_tuned(index, qj, k: int, tc: TuneConfig, backend: str,
                repeat: int) -> float:
    """Median wall-clock seconds of one setting (warmup excluded)."""
    d, _ = _run_tuned(index, qj, k, tc, backend)   # warmup / compile
    d.block_until_ready()
    ts = []
    for _ in range(max(1, repeat)):
        t0 = time.perf_counter()
        d, _ = _run_tuned(index, qj, k, tc, backend)
        d.block_until_ready()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def _bits(d, i) -> Tuple[bytes, bytes]:
    """The bitwise identity of a search answer (gate currency)."""
    return (np.asarray(d).tobytes(), np.asarray(i, np.int32).tobytes())


def autotune_index(index, *, queries=None, n_queries: int = 32,
                   k: int = 5, repeat: int = 3, quick: bool = False,
                   candidates: Optional[Sequence[TuneConfig]] = None,
                   backend: Optional[str] = None,
                   seed: int = 0) -> AutotuneTable:
    """Sweep refine-kernel knob candidates on the live device and return
    the winner as a one-entry AutotuneTable for this index's key.

    Each candidate is first GATED: its search output must be bitwise
    identical to the default-knob output on both backends ('pallas' and
    'ref') over the holdout batch; survivors are timed (`repeat` runs,
    median, warmup excluded) on `backend` (None = 'pallas', the tuned
    hot path) and the fastest wins.  The default config always survives
    its own gate, so the sweep always produces a winner.

    Args:
        index: the FreshIndex to tune (read-only).
        queries: explicit (Q, L) holdout batch; None synthesizes
            `n_queries` near-duplicates (`quality.holdout_queries`).
        n_queries: synthesized-holdout size when `queries` is None.
        k: result count the sweep times (latency is k-dependent only
            weakly; the gate re-proves exactness per candidate anyway).
        repeat: timed runs per surviving candidate (median taken).
        quick: shrink the candidate grid to the two-point CI smoke
            sweep (see `candidate_space`).
        candidates: explicit candidate list (None = `candidate_space`
            for the live platform's lowering, honoring `quick`).
        backend: backend to TIME with (None = 'pallas'); gating always
            checks both backends regardless.
        seed: holdout synthesis seed.
    Returns:
        AutotuneTable with one entry under this index's
        (device_kind, L, leaf_capacity, dtype) key, fingerprinted
        against the index content.
    """
    import jax.numpy as jnp

    from repro.quality.calibrate import holdout_queries, index_fingerprint

    q = (np.asarray(queries, np.float32) if queries is not None
         else holdout_queries(index, n_queries, seed=seed))
    if q.ndim == 1:
        q = q[None]
    qj = jnp.asarray(q)
    k = min(int(k), int(index.n_series))
    cands = (tuple(candidates) if candidates is not None
             else candidate_space(quick=quick))
    time_bk = backend if backend is not None else "pallas"

    base = TuneConfig()
    ref_bits = {bk: _bits(*_run_tuned(index, qj, k, base, bk))
                for bk in ("pallas", "ref")}

    survivors = []
    for tc in cands:
        if tc == base:
            survivors.append(tc)
            continue
        if all(_bits(*_run_tuned(index, qj, k, tc, bk)) == ref_bits[bk]
               for bk in ("pallas", "ref")):
            survivors.append(tc)

    timed = [(_time_tuned(index, qj, k, tc, time_bk, repeat), tc)
             for tc in survivors]
    baseline_s = next(t for t, tc in timed if tc == base)
    best_s, best = min(timed, key=lambda p: p[0])

    table = AutotuneTable(index_fingerprint(index))
    cfg = index.config
    table.put(device_kind(), index.series_len, cfg.leaf_capacity,
              cfg.dtype,
              TuneEntry(config=best, median_ms=best_s * 1e3,
                        baseline_ms=baseline_s * 1e3,
                        n_candidates=len(cands), n_exact=len(survivors)))
    return table
