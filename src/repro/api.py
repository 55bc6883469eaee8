"""Unified FreshIndex facade: one config-driven API for the whole index
lifecycle — build, k-NN search, incremental add, shard, checkpoint.

The paper frames FreSh as a modular pipeline of traverse-object stages
(BC -> TP -> PS/RS); this module is the single public surface over that
pipeline.  All tuning knobs live in one frozen `IndexConfig`; the
`FreshIndex` object carries them through every stage so segment counts,
bit depths and bounds can never silently disagree between build and query
time (the bug class `prepare_queries` used to have).

Quickstart::

    from repro.api import FreshIndex, IndexConfig

    index = FreshIndex.build(series)                     # defaults
    index = FreshIndex.build(series, IndexConfig(leaf_capacity=32,
                                                 bound="paabox"))
    dist, ids = index.search(queries, k=10)              # exact k-NN

    b = FreshIndex.builder(cfg, workers=4)               # streaming /
    for chunk in stream:                                 # lock-free
        b.feed(chunk)                                    # multi-worker
    index = b.finalize()                                 # build pipeline

    index.add(new_batch)          # delta-buffered, searchable immediately
    index.compact()               # incremental sorted-run merge

    index.shard(mesh)             # leaves block-sharded over mesh axis
    index.save("ckpt/")           # config + arrays
    index = FreshIndex.load("ckpt/")                     # no rebuild

Migration table (old free functions -> facade):

    ====================================  ================================
    old call                              new call
    ====================================  ================================
    build_index(x, leaf_capacity=...)     FreshIndex.build(x, IndexConfig(
                                              leaf_capacity=...))
    build_index over a stream / with      b = FreshIndex.builder(cfg,
      lock-free workers (no equivalent)       workers=4); b.feed(chunk);
                                              ...; b.finalize()
    build_index_host(x, executor)         IndexBuilder(cfg,
      (host demo forest, not queryable)       executor=executor) — same
                                              Refresh phases, real index
    search(idx, q)                        index.search(q)           (1-NN)
    search(idx, q, max_rounds=r)          index.search(q, max_rounds=r)
    (no k-NN equivalent)                  index.search(q, k=10)
    search_bruteforce(x, q)               search_bruteforce(x, q, k=...)
    shard_index(idx, mesh)  +             index.shard(mesh)  then
      make_sharded_search(mesh)(idx, q)     index.search(q, k=...)
    save_checkpoint(dir, step, idx)       index.save(dir)
    load_checkpoint(dir, like)            FreshIndex.load(dir)
    (no incremental insert)               index.add(batch); index.compact()
    index.search in a serving loop        engine = index.engine()
      (re-traces per (Q, k) shape)          fut = engine.submit(q, k=10)
                                            dist, ids = fut.result()
    (no defined add/search overlap)       engine.add(batch)  — snapshot-
                                            consistent: in-flight queries
                                            answer on their submit epoch
    make_sharded_search in a serving      index.shard(mesh).engine() —
      loop (re-traces, no epochs)           per-(bucket, k, mesh) AOT
                                            plans, mesh-wide epochs
    (no shard failure story)              engine.recover(ckpt_dir) —
                                            reload checkpoint arrays,
                                            re-mesh over survivors
    ====================================  ================================

The old functions remain importable from `repro.core` and are the engine
under this facade; calling `search` / `make_sharded_search` directly now
emits a DeprecationWarning pointing here.  For steady-state serving use
`index.engine(EngineConfig(...))` (`repro.serve`): micro-batched submits,
AOT-compiled per-bucket plans (zero re-traces after warmup), epoch
snapshots for concurrent inserts.

Incremental adds follow Jiffy's batch-update idea (lock-free skip list
with batch updates, arXiv:2102.01044): recent series live in an unsorted
delta buffer that every query scans EXACTLY (brute force) alongside the
pruned main index, and `compact()` merges the delta into the main index
with one INCREMENTAL sorted-run merge (`core.builder.merge_sorted_delta`)
that consumes the stored core arrays as-is — Jiffy's batch merge.  What
the merge eliminates versus the old bulk rebuild: re-normalization,
re-summarization, the global re-sort (the core run is binary-searched,
never re-sorted) and half-precision re-rounding; the array bytes still
transit the host once per compact.  Search results are therefore always
exact, with or without a pending delta.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Iterable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.analysis.hooks import observe
from repro.checkpoint.store import load_arrays, save_checkpoint
from repro.core import isax
from repro.core.builder import IndexBuilder, merge_sorted_delta
from repro.core.index import (FlatIndex, build_index, index_stats,
                              pad_leaves)
from repro.core.search import (build_sharded_search, merge_delta_topk,
                               search_plan, shard_index, squeeze_k)
from repro.maintenance.tombstones import (core_dead_mask, delta_alive_mask,
                                          mask_core)
from repro.quality.calibrate import CalibrationTable, index_fingerprint
from repro.quality.stop_rules import EXACT, StopRule
from repro.runtime.sharding import mesh_sig

_BOUNDS = ("prefix", "symbox", "paabox")
_BACKENDS = ("ref", "pallas")
_DTYPES = ("float32", "bfloat16", "float16")


@dataclasses.dataclass(frozen=True)
class IndexConfig:
    """Every knob of the index lifecycle in one frozen, hashable place.

    segments       PAA/iSAX word length w (series length must divide by it)
    bits           symbol cardinality 2^bits
    leaf_capacity  series per flat leaf
    bound          leaf lower bound: 'prefix' (paper MINDIST) | 'symbox'
                   | 'paabox' (tightest)
    znorm          z-normalize series and queries (the paper's setting)
    dtype          storage dtype of the series matrix; search math is f32
    backend        summarization/pruning/refinement kernels: 'pallas'
                   (Mosaic on TPU, interpret elsewhere; refinement runs
                   the fused allocation-free kernels.refine_topk) | 'ref'
                   (pure jnp, materializes the (Q, K*M, L) gather)
    round_leaves   leaves refined per query per refinement round (K);
                   None (default) = resolve through a fresh AutotuneTable
                   when installed, else the static default of 8
    pq_budget      cap on leaves admitted to the per-query priority queue
                   (None = the exact round budget; smaller values trade
                   exactness for PQ setup time, like max_rounds)
    dma_depth      Mosaic refine-kernel HBM->VMEM DMA ring depth (pallas
                   backend only; 1 = pipelined BlockSpec kernel, >= 2 =
                   explicit multi-buffered ring); None = autotune/default

    Unset (None) knobs resolve per `FreshIndex.search_knobs`: a fresh
    `kernels.autotune.AutotuneTable` entry for this device/shape when
    one is installed, else the static defaults — unknown devices and
    untuned indexes behave exactly as before autotune existed.
    """
    segments: int = isax.SEGMENTS
    bits: int = isax.SAX_BITS
    leaf_capacity: int = 64
    bound: str = "prefix"
    znorm: bool = True
    dtype: str = "float32"
    backend: str = "ref"
    round_leaves: Optional[int] = None
    pq_budget: Optional[int] = None
    dma_depth: Optional[int] = None

    def __post_init__(self):
        if self.bound not in _BOUNDS:
            raise ValueError(f"bound must be one of {_BOUNDS}, "
                             f"got {self.bound!r}")
        if self.backend not in _BACKENDS:
            raise ValueError(f"backend must be one of {_BACKENDS}, "
                             f"got {self.backend!r}")
        if self.dtype not in _DTYPES:
            raise ValueError(f"dtype must be one of {_DTYPES}, "
                             f"got {self.dtype!r}")
        if self.segments < 1 or self.bits < 1 or self.bits > 8:
            raise ValueError("need segments >= 1 and 1 <= bits <= 8")
        if self.leaf_capacity < 1:
            raise ValueError("leaf_capacity must be >= 1")
        if self.round_leaves is not None and self.round_leaves < 1:
            raise ValueError("round_leaves must be >= 1 or None")
        if self.pq_budget is not None and self.pq_budget < 1:
            raise ValueError("pq_budget must be >= 1 or None")
        if self.dma_depth is not None and self.dma_depth < 1:
            raise ValueError("dma_depth must be >= 1 or None")

    def validate_series_len(self, L: int) -> None:
        """Raise ValueError unless series length L divides into
        `segments` equal PAA frames (the iSAX word requirement)."""
        if L % self.segments != 0:
            raise ValueError(
                f"series length {L} is not divisible by segments="
                f"{self.segments}; pick a divisor or pad the series")

    def to_dict(self) -> dict:
        """Plain-dict form of every field (what checkpoints persist)."""
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "IndexConfig":
        """Rebuild a config from `to_dict()` output; unknown keys in `d`
        are ignored so old checkpoints load under newer configs."""
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})


class FreshIndex:
    """The index lifecycle object.  Construct via build() or load()."""

    def __init__(self, idx: FlatIndex, config: IndexConfig):
        self._idx = idx
        self.config = config
        # No host copy of the dataset is retained: compact() merges the
        # delta against the STORED index arrays in place (incremental
        # sorted-run merge, core.builder.merge_sorted_delta), so the
        # facade adds O(1) memory on top of the device-resident index.
        self._n_base = int(jnp.sum(idx.valid))
        self._delta: list = []                  # pending unsorted batches
        self._delta_cat = None                  # jnp concat cache
        self._mesh = None
        self._mesh_axis = "data"
        self._sharded_fns: dict = {}            # (k, round_leaves, ...) -> fn
        # ---- lifecycle (repro.maintenance): ids are STABLE and never
        # reused — `_next_id` only grows, delta position p holds id
        # `_delta_id0 + p`, and after a tombstone-dropping compaction the
        # id space is sparse (a dropped id can never resurrect).
        self._next_id = self._n_base
        self._delta_id0 = self._n_base
        self._tombstones: set = set()           # logically-deleted ids
        self._ttl: dict = {}                    # id -> monotonic deadline
        self._first_tombstone_at: Optional[float] = None
        self._masked = None                     # search_view cache ...
        self._masked_key = None                 # ... keyed (ver, pending)
        self._lifecycle_ver = 0
        # ---- in-place update (stable ids): update(sid, x) retires the
        # old row and introduces the new one under a fresh INTERNAL id,
        # but keeps answering as `sid`.  `_id_map` is stable -> current
        # internal, `_alias` the inverse (internal -> stable, only for
        # renamed rows); both empty until the first update().
        self._id_map: dict = {}
        self._alias: dict = {}
        # ---- approximate search (repro.quality): fitted stop rules,
        # installed by calibrate() or restored by load()
        self._calibration: Optional[CalibrationTable] = None
        # ---- backend autotune (repro.kernels.autotune): measured knob
        # winners, installed by autotune() or restored by load()
        self._autotune = None
        self._fp = None                         # fingerprint cache ...
        self._fp_key = None                     # ... keyed (ver, pending)

    # ------------------------------------------------------------------ #
    # construction
    # ------------------------------------------------------------------ #
    @classmethod
    def build(cls, data, config: Optional[IndexConfig] = None,
              **overrides) -> "FreshIndex":
        """Bulk-build an index over `data`, an (n, L) float array.

        Args:
            data: (n, L) series matrix (host or device array; a device
                array is built in place, never copied through the host);
                n == 0 is the legal bootstrap.
            config: IndexConfig (None = defaults).
            **overrides: IndexConfig fields, so the two spellings
                `build(x, IndexConfig(leaf_capacity=32))` and
                `build(x, leaf_capacity=32)` are equivalent.
        Returns:
            A new FreshIndex over a freshly built FlatIndex.
        Raises:
            ValueError: data is not 2-D, or L fails
                `config.validate_series_len`.

        Dispatches to the fused single-program `build_index` jit — the
        fastest one-shot path.  The `IndexBuilder` phase pipeline
        (streaming feed, lock-free multi-worker builds via
        `FreshIndex.builder`, incremental compaction) produces
        bit-identical arrays, proven by tests/test_builder.py::
        test_pipeline_matches_fused_build, so the two entry points are
        interchangeable; an empty (0, L) bootstrap build goes through
        the builder (the fused program needs at least one row).

        Concurrency: pure construction — no shared state until the
        returned index is handed to readers.
        """
        cfg = config or IndexConfig()
        if overrides:
            cfg = dataclasses.replace(cfg, **overrides)
        if not isinstance(data, jax.Array):
            data = np.asarray(data)     # a device array stays where it is
        if data.ndim != 2:
            raise ValueError(f"data must be (n, L), got shape {data.shape}")
        if data.shape[0] == 0:
            return cls.builder(cfg).feed(np.asarray(data)).finalize()
        cfg.validate_series_len(data.shape[1])
        idx = build_index(jnp.asarray(data), segments=cfg.segments,
                          bits=cfg.bits, leaf_capacity=cfg.leaf_capacity,
                          znorm=cfg.znorm, bound=cfg.bound,
                          backend=cfg.backend)
        if cfg.dtype != "float32":
            dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float16
            idx = idx._replace(series=idx.series.astype(dt))
        return cls(idx, cfg)

    @classmethod
    def builder(cls, config: Optional[IndexConfig] = None,
                **builder_kwargs) -> IndexBuilder:
        """An `IndexBuilder` for streaming / multi-worker construction::

            b = FreshIndex.builder(cfg, workers=4)
            for chunk in stream:
                b.feed(chunk)
            index = b.finalize()

        Args:
            config: IndexConfig for the built index (None = defaults).
            **builder_kwargs: pass through (workers, part_rows,
                injectors, executor) — see
                `repro.core.builder.IndexBuilder`.
        Returns:
            A fresh single-use IndexBuilder.

        Concurrency: the builder spawns its own lock-free Refresh
        workers when `workers >= 2`; feed()/finalize() themselves are
        single-caller (see IndexBuilder).
        """
        return IndexBuilder(config, **builder_kwargs)

    # ------------------------------------------------------------------ #
    # introspection
    # ------------------------------------------------------------------ #
    @property
    def index(self) -> FlatIndex:
        """The underlying device-resident FlatIndex (read-only use)."""
        return self._idx

    @property
    def n_series(self) -> int:
        """Total searchable series: compacted core + pending delta,
        MINUS logically-deleted (tombstoned) series — what k may not
        exceed.  Tombstoned rows stay physical until compact()."""
        return self._n_base + self.n_pending - len(self._tombstones)

    @property
    def n_pending(self) -> int:
        """Rows sitting in the uncompacted delta buffer (tombstoned
        delta rows included — they are still physically pending)."""
        return sum(b.shape[0] for b in self._delta)

    @property
    def n_deleted(self) -> int:
        """Live tombstones: logically deleted, not yet physically
        dropped by compact()."""
        return len(self._tombstones)

    @property
    def n_ttl(self) -> int:
        """Series carrying a pending TTL deadline."""
        return len(self._ttl)

    @property
    def series_len(self) -> int:
        """Length L of every indexed series (and of valid queries)."""
        return self._idx.series.shape[1]

    @property
    def mesh(self):
        """The jax Mesh this index is sharded over; None when unsharded."""
        return self._mesh

    @property
    def mesh_axis(self) -> str:
        """Mesh axis name the leaves are block-sharded over ('data' by
        default; meaningful only while `mesh` is not None)."""
        return self._mesh_axis

    def stats(self) -> dict:
        """Host-side summary (leaf count/fill, pending rows, sharded?).

        Concurrency: read-only; may observe a concurrent writer's
        intermediate delta count — serialize externally if you need a
        consistent cut (the serving engine does).

        What the searches did is kept apart, process-wide (it outlives
        the index): `repro.obs.totals()` sums the last
        `repro.obs.CAPACITY` searches through this facade — searches,
        queries, rounds, live query-rounds (a query is live in a round
        when its next unrefined lower bound beats its k-th best so far)
        and refined (query, leaf) pairs; `repro.obs.records()` holds
        them call by call.  A sharded index's calls count searches and
        queries only.
        """
        st = index_stats(self._idx)
        st["n_pending"] = self.n_pending
        st["sharded"] = self._mesh is not None
        st["n_deleted"] = self.n_deleted
        st["n_ttl"] = self.n_ttl
        st["n_aliases"] = len(self._alias)
        st["calibrated"] = self._calibration is not None
        st["autotuned"] = self._autotune is not None
        return st

    def __repr__(self) -> str:
        return (f"FreshIndex(n={self.n_series}, L={self.series_len}, "
                f"pending={self.n_pending}, config={self.config})")

    # ------------------------------------------------------------------ #
    # search
    # ------------------------------------------------------------------ #
    def search(self, queries, k: int = 1, *,
               mode: str = "exact", recall_target: float = 0.95,
               stop_eps: Optional[float] = None,
               max_leaves: Optional[int] = None,
               round_leaves: Optional[int] = None, sync_every: int = 1,
               max_rounds: Optional[int] = None,
               pq_budget: Optional[int] = None,
               backend: Optional[str] = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """k-NN over `queries` ((L,) or (Q, L) float array).

        Returns:
            (dist, ids): shape (Q,) for k == 1, (Q, k) ascending by
            distance otherwise.  Any pending delta buffer is scanned
            exactly and merged in, so adds are visible immediately,
            before compact().  Logically-deleted / TTL-expired series
            never appear: the search runs over the tombstone-masked
            view (`search_view`), bit-identical to the tombstone-aware
            brute-force oracle.  Reported distances are always TRUE
            distances to the returned series, in both modes.
        Raises:
            ValueError: query length != series_len, k < 1, k exceeds
                n_series (which excludes tombstoned series), or
                mode/stop-rule arguments are inconsistent (see
                `resolve_stop_rule`).

        `mode` selects the quality tier: "exact" (default, certified
        k-NN) or "approx" — early-terminate the round loop under a
        `repro.quality.StopRule`, either given explicitly (`stop_eps` /
        `max_leaves`) or resolved from this index's calibration table
        as the cheapest fitted rule whose MEASURED recall@k met
        `recall_target` (run `calibrate()` first, or load a calibrated
        checkpoint).  `max_rounds` caps the refinement loop the blunt
        way (distances become upper bounds).  round_leaves / pq_budget
        / the kernel backend default from this index's IndexConfig,
        with UNSET config knobs resolved through a fresh autotune table
        when one is installed — see `search_knobs` (pass explicit
        values to override per call).  On a sharded
        index `sync_every` sets the expeditive/standard all-reduce
        cadence and `sync_every` participates in the per-mesh
        compiled-search cache key (unsharded searches ignore it).

        Concurrency: a reader.  Safe against other readers; racing a
        writer (add/compact) has NO defined ordering on this facade —
        use `engine()` for snapshot-consistent concurrent add/search.
        """
        with jax.profiler.TraceAnnotation(obs.SEARCH_SPAN):
            with jax.profiler.TraceAnnotation(obs.PREPARE_SPAN):
                q, rule, rl, pqb, bk, dd = self._search_args(
                    queries, k, mode, recall_target, stop_eps, max_leaves,
                    round_leaves, pq_budget, backend)
                core, delta, alive, id0 = self.search_view()
            if self._mesh is not None:
                # the mesh placement is part of the key (not just cleared
                # on shard()): a compiled shard_map search can never be
                # replayed against arrays living on a different placement
                key = (k, rl, sync_every, max_rounds, pqb,
                       bk, dd, rule, mesh_sig(self._mesh))
                fn = self._sharded_fns.get(key)
                if fn is None:
                    fn = build_sharded_search(
                        self._mesh, axis=self._mesh_axis, k=k,
                        round_leaves=rl, sync_every=sync_every,
                        max_rounds=max_rounds, znorm=self.config.znorm,
                        pq_budget=pqb, backend=bk,
                        dma_depth=dd, config=self.config, **rule.lower())
                    self._sharded_fns[key] = fn
                d, i = fn(core, q)
                counts = None             # the sharded plan counts nothing
            else:
                # the knobs are resolved: dispatch the jitted plan
                d, i, counts = search_plan(
                    core, q, k=k, round_leaves=rl, znorm=self.config.znorm,
                    max_rounds=max_rounds, pq_budget=pqb, backend=bk,
                    dma_depth=dd, **rule.lower())
                d, i = squeeze_k(d, i, k)
            if delta is not None:
                # fold the exact delta scan into the core answer.  The
                # core search program stays cached across add() calls;
                # only the small merge re-jits when the delta row count
                # changes.  (The serving layer instead AOT-compiles the
                # fused snapshot_search once per published epoch — same
                # math, different compile amortization.)
                d2 = d[:, None] if k == 1 else d
                i2 = i[:, None] if k == 1 else i
                md, mi = merge_delta_topk(delta, q, d2, i2, alive, k=k,
                                          n_base=id0,
                                          znorm=self.config.znorm)
                d, i = squeeze_k(md, mi, k)
            if self._alias:
                i = jnp.asarray(self._remap_ids(np.asarray(i)))
            obs.record(q.shape[0], rl, counts)
            return d, i

    def _search_args(self, queries, k, mode, recall_target, stop_eps,
                     max_leaves, round_leaves, pq_budget, backend):
        """`search`'s checked queries, stop rule and knobs: (q, rule,
        round_leaves, pq_budget, backend, dma_depth)."""
        q = jnp.asarray(queries, jnp.float32)
        if q.ndim == 1:
            q = q[None]
        if q.shape[-1] != self.series_len:
            raise ValueError(
                f"queries have length {q.shape[-1]}, index holds series of "
                f"length {self.series_len}")
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        if k > self.n_series:
            raise ValueError(f"k={k} exceeds the {self.n_series} indexed "
                             f"series")
        rule = self.resolve_stop_rule(mode, k=k, recall_target=recall_target,
                                      stop_eps=stop_eps,
                                      max_leaves=max_leaves)
        # resolve every search knob NOW (explicit arg > IndexConfig >
        # fresh autotune table > static default) so the compiled-search
        # cache keys on VALUES — a retuned table changes the key, never
        # silently re-resolves under a stale compiled fn
        kn = self.search_knobs()
        rl = round_leaves if round_leaves is not None else kn.round_leaves
        pqb = pq_budget if pq_budget is not None else kn.pq_budget
        bk = backend if backend is not None else self.config.backend
        dd = kn.dma_depth if bk == "pallas" else 1
        return q, rule, rl, pqb, bk, dd

    def resolve_stop_rule(self, mode: str, *, k: int,
                          recall_target: float = 0.95,
                          stop_eps: Optional[float] = None,
                          max_leaves: Optional[int] = None) -> StopRule:
        """The `StopRule` a (mode, k, recall_target) request lowers to —
        the ONE resolution path search() and the serving engine's
        latency tiers share.

        Args:
            mode: "exact" or "approx".
            k: result count the rule will serve (calibration entries are
                per-k).
            recall_target: measured recall@k floor used for the
                calibration-table lookup (ignored when explicit knobs
                are given).
            stop_eps: explicit BSF-convergence slack; with "approx",
                overrides the table.
            max_leaves: explicit visited-leaf cap; with "approx",
                overrides the table.
        Returns:
            The resolved StopRule (`quality.EXACT` for exact mode).
        Raises:
            ValueError: unknown mode; explicit knobs passed with
                mode="exact"; or mode="approx" with no explicit knobs
                and no calibration entry for (k, recall_target).

        Concurrency: read-only on calibration state; serialize against
        `calibrate()` like any reader against a writer.
        """
        if mode not in ("exact", "approx"):
            raise ValueError(f"mode must be 'exact' or 'approx', "
                             f"got {mode!r}")
        if mode == "exact":
            if stop_eps is not None or max_leaves is not None:
                raise ValueError(
                    "stop_eps/max_leaves are approx-mode knobs; they "
                    "contradict mode='exact'")
            return EXACT
        if stop_eps is not None or max_leaves is not None:
            return StopRule(eps=stop_eps if stop_eps is not None else 0.0,
                            max_leaves=max_leaves)
        if self._calibration is None:
            raise ValueError(
                "mode='approx' needs either explicit stop_eps/max_leaves "
                "or a fitted calibration table — run index.calibrate() "
                "(or load a calibrated checkpoint)")
        entry = self._calibration.lookup(k, recall_target)
        if entry is None:
            raise ValueError(
                f"no calibration entry for (k={k}, recall_target="
                f"{recall_target}); re-run calibrate() with ks/targets "
                f"covering it, or pass explicit stop_eps/max_leaves")
        return entry.rule

    def calibrate(self, **kwargs) -> CalibrationTable:
        """Fit approximate-search stop rules for this index and install
        the resulting table (see `repro.quality.calibrate.calibrate` for
        every argument: ks, targets, queries/n_queries, eps_grid,
        leaves_grid, ...).  The installed table is what
        `search(mode="approx")` and `EngineConfig.latency_tiers` resolve
        rules from, and `save()` persists it with the checkpoint.

        Args:
            **kwargs: forwarded verbatim to the offline calibrator.
        Returns:
            The fitted CalibrationTable (also stored on the index).

        Concurrency: a writer of calibration state (and a reader of the
        index); serialize against other writers like add().
        """
        from repro.quality.calibrate import calibrate as _fit
        table = _fit(self, **kwargs)
        self._calibration = table
        return table

    @property
    def calibration(self) -> Optional[CalibrationTable]:
        """The installed CalibrationTable (None until calibrate() runs
        or a calibrated checkpoint is loaded)."""
        return self._calibration

    def is_calibration_fresh(self) -> bool:
        """True when the installed calibration table was measured on
        EXACTLY this index content (fingerprints match) — i.e. its
        advertised recalls still describe what approx search returns.
        Mutations (add/delete/update/compact) make it stale; stale
        tables still resolve (documented degradation) but stats surface
        this flag so operators can re-calibrate.

        Concurrency: a reader; the fingerprint is cached per lifecycle
        version, so repeated calls are cheap.
        """
        if self._calibration is None:
            return False
        return self._fingerprint() == self._calibration.fingerprint

    def _fingerprint(self) -> str:
        """The content fingerprint, cached per lifecycle version (shared
        by the calibration and autotune freshness checks)."""
        key = (self._lifecycle_ver, self.n_pending)
        if self._fp_key != key:
            self._fp = index_fingerprint(self)
            self._fp_key = key
        return self._fp

    # ------------------------------------------------------------------ #
    # backend autotune (repro.kernels.autotune)
    # ------------------------------------------------------------------ #
    def autotune(self, **kwargs) -> "AutotuneTable":
        """Sweep refine-kernel knob candidates on the live device and
        install the winning AutotuneTable (see
        `repro.kernels.autotune.autotune_index` for every argument:
        queries, n_queries, k, repeat, quick, candidates, backend,
        seed).  Every candidate is gated on BITWISE equality with the
        default-knob search output before it may win, so installing the
        table never changes any search result — only its latency.  The
        installed table is what `search_knobs` resolves unset
        IndexConfig knobs through, and `save()` persists it with the
        checkpoint.

        Args:
            **kwargs: forwarded verbatim to the sweep harness.
        Returns:
            The measured AutotuneTable (also stored on the index).

        Concurrency: a writer of autotune state (and a reader of the
        index); serialize against writers like calibrate().
        """
        from repro.kernels.autotune import autotune_index
        table = autotune_index(self, **kwargs)
        self._autotune = table
        return table

    @property
    def autotune_table(self):
        """The installed AutotuneTable (None until autotune() runs or a
        tuned checkpoint is loaded)."""
        return self._autotune

    def is_autotune_fresh(self) -> bool:
        """True when the installed autotune table was measured on
        EXACTLY this index content (fingerprints match).  Mutations
        (add/delete/update/compact) make it stale; a stale table is NOT
        resolved through — `search_knobs` falls back to the static
        defaults, the conservative direction, until a re-tune (timings
        are content-dependent, and silently serving a config tuned for
        different content is how perf regressions hide).

        Concurrency: a reader; the fingerprint is cached per lifecycle
        version, so repeated calls are cheap.
        """
        if self._autotune is None:
            return False
        return self._fingerprint() == self._autotune.fingerprint

    def search_knobs(self) -> "TuneConfig":
        """The fully-resolved search knobs this index serves with, as a
        `kernels.autotune.TuneConfig`: each knob is the IndexConfig
        field when set, else the FRESH autotune-table entry for this
        (device_kind, L, leaf_capacity, dtype) when one is installed,
        else the static default (`kernels.autotune.DEFAULTS`) — so an
        untuned index, an unknown device, or a stale table all behave
        exactly as before autotune existed.  This is the ONE resolution
        path search(), the serving engine's Knobs, and the calibrator
        share.

        Concurrency: a reader (of config + autotune state); safe
        against other readers, serialize against autotune()/reload()
        like any reader against a writer.
        """
        from repro.kernels.autotune import device_kind, resolve_knobs
        entry = None
        if self._autotune is not None and self.is_autotune_fresh():
            entry = self._autotune.lookup(
                device_kind(), self.series_len,
                self.config.leaf_capacity, self.config.dtype)
        return resolve_knobs(self.config, entry)

    def _remap_ids(self, ids: np.ndarray) -> np.ndarray:
        """Internal -> stable id remap at the result boundary: rows
        renamed by update() answer under their stable public id.  Host
        numpy, O(#aliases) passes; the no-alias fast path returns the
        input untouched (exact mode stays bit-identical until the first
        update())."""
        if not self._alias:
            return ids
        out = np.array(ids, np.int32, copy=True)
        for internal, stable in self._alias.items():
            out[out == internal] = stable
        return out

    def search_view(self):
        """The tombstone-masked search inputs, as one consistent tuple
        `(core, delta, delta_alive, delta_id0)`:

        core         the FlatIndex to search — the stored index itself
                     when nothing is deleted, else a derived view whose
                     dead rows carry the never-wins sentinel norm
                     (`maintenance.mask_core`; stored arrays untouched,
                     shapes unchanged, so compiled plans are reusable)
        delta        pending rows as one (m, L) device array (None when
                     empty) — `delta_cat`
        delta_alive  (m,) bool device mask, False on tombstoned delta
                     rows (None when all alive)
        delta_id0    the delta id offset: delta position p is series id
                     `delta_id0 + p`

        This is what `search()` consumes and what the serving engine
        captures into each published Snapshot.  The masked view is
        cached until the next lifecycle change (delete / TTL expiry /
        add / compact).

        Concurrency: a reader; serialize against writers like search().
        """
        key = (self._lifecycle_ver, self.n_pending)
        if self._masked_key != key:
            if self._tombstones:
                dead = core_dead_mask(np.asarray(self._idx.perm),
                                      self._tombstones)
                core = mask_core(self._idx, dead)
                alive = delta_alive_mask(self.n_pending, self._delta_id0,
                                         self._tombstones)
            else:
                core, alive = self._idx, None
            self._masked = (core, alive)
            self._masked_key = key
        core, alive = self._masked
        return core, self.delta_cat, alive, self._delta_id0

    @property
    def delta_cat(self) -> Optional[jnp.ndarray]:
        """The pending delta as one (m, L) device array (None when empty);
        concatenation is cached between add() calls."""
        if not self._delta:
            return None
        if self._delta_cat is None:
            # blocking host->device transfer: the race checker asserts
            # this observe never fires while the engine's _cv is held
            observe("index.delta_cat", self)
            self._delta_cat = jnp.asarray(
                np.concatenate(self._delta, axis=0))
        return self._delta_cat

    # ------------------------------------------------------------------ #
    # serving
    # ------------------------------------------------------------------ #
    def engine(self, config: Optional["EngineConfig"] = None,
               **overrides) -> "QueryEngine":
        """A serving-layer QueryEngine over this index: micro-batched
        `submit(q, k=...)` futures, AOT-compiled per-bucket search plans
        (steady state never re-traces), and snapshot-consistent
        concurrent add().  Serves local AND sharded indexes — a sharded
        index gets per-(bucket, k, mesh placement) plans, mesh-wide
        epoch snapshots and elastic `recover()` (see docs/SERVING.md).

        Args:
            config: EngineConfig (None = defaults).
            **overrides: EngineConfig fields, mirroring build().
        Returns:
            A started QueryEngine bound to this index.

        Concurrency: the engine serializes all writers to this index
        through its own locks; do not mutate the index out-of-band
        while an engine serves it (or call `engine.refresh()` after).
        """
        from repro.serve import EngineConfig, QueryEngine
        cfg = config or EngineConfig()
        if overrides:
            cfg = dataclasses.replace(cfg, **overrides)
        return QueryEngine(self, cfg)

    # ------------------------------------------------------------------ #
    # incremental updates (Jiffy-style batch delta)
    # ------------------------------------------------------------------ #
    def add(self, batch, *, ttl_s: Optional[float] = None) -> "FreshIndex":
        """Append `batch` ((L,) or (m, L)) to the delta buffer.  O(1),
        no rebuild; the rows are immediately visible to search() via an
        exact delta scan.  Ids continue from the monotone id counter
        (contiguous with the existing series until the first
        tombstone-dropping compaction makes the id space sparse).

        `ttl_s` gives every row of THIS batch a time-to-live: after
        `ttl_s` seconds the rows become tombstones at the next
        `expire_ttl()` sweep (the engine's MaintenancePolicy schedules
        sweeps; a TTL'd series thus stays visible at most
        ttl_s + sweep_interval).

        Raises:
            ValueError: batch shape does not match (m, series_len), or
                ttl_s is not positive.

        Concurrency: a writer.  Not safe against concurrent readers or
        writers on this facade — the engine's add() wraps it in the
        writer lock and publishes an epoch instead.
        """
        if ttl_s is not None and ttl_s <= 0:
            raise ValueError(f"ttl_s must be > 0 or None, got {ttl_s}")
        # np.array (not asarray): the delta buffer must own its rows — a
        # caller reusing its batch buffer between add()s would otherwise
        # silently rewrite pending series before search/compact reads them
        b = np.array(batch, np.float32)
        if b.ndim == 1:
            b = b[None]
        if b.ndim != 2 or b.shape[1] != self.series_len:
            raise ValueError(
                f"batch must be (m, {self.series_len}), got {b.shape}")
        first_id = self._delta_id0 + self.n_pending
        self._delta.append(b)
        self._delta_cat = None
        self._next_id += b.shape[0]
        if ttl_s is not None:
            deadline = time.monotonic() + ttl_s
            for sid in range(first_id, first_id + b.shape[0]):
                self._ttl[sid] = deadline
        return self

    def update(self, sid: int, series, *,
               ttl_s: Optional[float] = None) -> "FreshIndex":
        """Replace series `sid`'s values in place, under its STABLE id:
        the old row is retired (tombstoned, physically dropped at the
        next compact) and the new values are introduced in the same
        call, but search keeps answering with id `sid` — not
        delete-then-add's two visible ids.  Internally the new row gets
        a fresh never-reused id (the tombstone machinery stays
        exactly-once) and an alias maps it back to `sid` at the result
        boundary; the alias survives compaction and checkpoints.

        Args:
            sid: the stable id to update (a currently-live series).
            series: the new (L,) values.
            ttl_s: optional time-to-live for the NEW values (the old
                row's TTL, if any, dies with it).
        Returns:
            self (fluent, like add()).
        Raises:
            ValueError: `sid` was never assigned or is not currently
                live (deleted/expired/never existed), or `series` has
                the wrong length.

        Concurrency: a writer.  On this facade the retire+introduce
        pair is NOT atomic against concurrent readers — the engine's
        `update()` wraps it in the writer lock and publishes BOTH sides
        as one epoch, so engine readers never observe zero or two live
        rows for `sid`.
        """
        sid = int(sid)
        cur = self._id_map.get(sid, sid)
        row = np.asarray(series, np.float32)
        if row.ndim != 1 or row.shape[0] != self.series_len:
            raise ValueError(
                f"series must be ({self.series_len},), got {row.shape}")
        if self.delete(cur) == 0:
            raise ValueError(
                f"id {sid} is not a live series; update() replaces an "
                f"existing row (use add() for new series)")
        internal = self._delta_id0 + self.n_pending
        self.add(row, ttl_s=ttl_s)
        # delete(cur) popped cur's own alias (if sid was updated
        # before); rebind the stable id to the fresh internal row
        self._id_map[sid] = internal
        self._alias[internal] = sid
        return self

    # ------------------------------------------------------------------ #
    # lifecycle (repro.maintenance): logical deletion + TTL expiry
    # ------------------------------------------------------------------ #
    def delete(self, ids: Union[int, Iterable[int]]) -> int:
        """Logically delete series by id: tombstoned rows stop matching
        any search immediately (masked to the never-wins sentinel, see
        `repro.maintenance.tombstones`) and are physically dropped —
        exactly once — by the next compact().  Ids are never reused, so
        a deleted id can never resurrect.

        Idempotent: already-tombstoned or already-dropped ids are
        skipped.  Returns the number of NEWLY tombstoned series.

        Raises:
            ValueError: an id is negative or was never assigned.

        Concurrency: a writer — serialize like add() (the engine's
        delete() wraps this in its writer lock and publishes an epoch).
        """
        if isinstance(ids, (int, np.integer)):
            ids = (int(ids),)
        core_ids = None                     # host perm pulled at most once
        d_lo, d_hi = self._delta_id0, self._delta_id0 + self.n_pending
        newly = 0
        for sid in ids:
            # a stable id renamed by update() resolves to the internal
            # row currently carrying it
            sid = self._id_map.get(int(sid), int(sid))
            if sid < 0 or sid >= self._next_id:
                raise ValueError(
                    f"id {sid} was never assigned (ids run 0.."
                    f"{self._next_id - 1})")
            if sid in self._tombstones:
                continue
            if not d_lo <= sid < d_hi:
                if core_ids is None:
                    perm = np.asarray(self._idx.perm)
                    valid = np.asarray(self._idx.valid)
                    core_ids = set(perm[valid].tolist())
                if sid not in core_ids:
                    continue                # already dropped by a compact
            self._tombstones.add(sid)
            self._ttl.pop(sid, None)
            stable = self._alias.pop(sid, None)
            if stable is not None:
                self._id_map.pop(stable, None)
            newly += 1
        if newly:
            if self._first_tombstone_at is None:
                self._first_tombstone_at = time.monotonic()
            self._lifecycle_ver += 1
        return newly

    def expire_ttl(self, now: Optional[float] = None) -> int:
        """Convert every TTL whose deadline has passed into a tombstone
        (the TTL expiry sweep — `MaintenancePolicy` schedules this on
        the freshness class's `sweep_interval_s`).  `now` is a
        `time.monotonic()` value (None = current time; tests pass an
        explicit clock).  Returns the number of series expired.

        Concurrency: a writer — serialize like delete().
        """
        if now is None:
            now = time.monotonic()
        expired = [sid for sid, dl in self._ttl.items() if dl <= now]
        return self.delete(expired) if expired else 0

    @property
    def tombstone_age_s(self) -> float:
        """Seconds since the oldest live tombstone was created (0.0 when
        none) — what `MaintenancePolicy.due` compares to the freshness
        class's `staleness_budget_s`."""
        if self._first_tombstone_at is None:
            return 0.0
        return time.monotonic() - self._first_tombstone_at

    def compact(self) -> "FreshIndex":
        """Merge the delta buffer into the main index with ONE incremental
        sorted-run merge (`core.builder.merge_sorted_delta`, Jiffy's batch
        merge).  The stored core arrays are consumed AS-IS — series, PAA,
        iSAX words, squared norms and ids of already-indexed rows are
        bit-preserved: no reconstruction into original order, no
        re-normalization, no re-summarization, no re-sort (the delta run
        is binary-searched into the sorted core) — and only the delta is
        normalized + summarized (once, float32) and cast to the storage
        dtype (once).  With
        float32 storage the result is bit-identical to a fresh build over
        the concatenated data; with half storage (bfloat16/float16) each
        series is rounded exactly once, at its first compact, so repeated
        compacts are drift-free: compact∘compact == compact.

        Concurrency: a writer (prepare + commit back to back).  Not safe
        against concurrent use of this facade; the engine splits the
        pair so the heavy merge runs outside its reader lock.
        """
        return self.commit_compact(self.prepare_compact())

    def prepare_compact(self):
        """Compute the compacted core WITHOUT mutating this index — the
        heavy merge can then run outside a serving lock (QueryEngine.add
        does this for auto-compaction).  Returns an opaque token for
        commit_compact(), or None when there is no pending delta AND no
        live tombstone (nothing to merge, nothing to drop).

        Tombstoned ids are passed to the merge as `drop_ids`, so the
        prepared core has them physically removed; commit_compact()
        refuses the token if the tombstone set changed in between
        (exactly-once drop).

        Concurrency: read-only preparation; the caller must prevent any
        writer from changing the delta or tombstones between prepare and
        commit (the engine holds its writer lock across the pair).
        """
        drops = frozenset(self._tombstones)
        if not self._delta and not drops:
            return None
        delta = (np.concatenate(self._delta, axis=0) if self._delta
                 else np.zeros((0, self.series_len), np.float32))
        merged = merge_sorted_delta(self._idx, delta, self.config,
                                    drop_ids=drops or None,
                                    delta_id0=self._delta_id0)
        if self._mesh is not None:
            # pre-place the merged core over the current mesh HERE, in
            # the heavy phase: commit_compact's re-shard then finds the
            # arrays already carrying the target sharding and its
            # device_puts are no-ops, keeping the commit cheap under a
            # serving lock (readers never stall behind the placement)
            n_dev = self._mesh.shape[self._mesh_axis]
            merged = shard_index(pad_leaves(merged, n_dev), self._mesh,
                                 axis=self._mesh_axis)
        return (merged, delta.shape[0], len(self._delta), drops)

    def commit_compact(self, token) -> "FreshIndex":
        """Install a prepare_compact() result `token` (O(1) pointer swap
        plus, for sharded indexes, the re-shard device_puts).  Clears
        the tombstone set the merge dropped and advances the delta id
        offset to the monotone high-water mark, so dropped ids stay
        retired forever.

        Raises:
            RuntimeError: the delta or the tombstone set changed since
                the token was prepared (a raced add/delete) — raised
                instead of dropping newer series or dropping a
                tombstone zero or two times.

        Concurrency: a writer; the caller must serialize the
        prepare/commit pair against every other writer (the engine's
        writer lock does).
        """
        if token is None:
            return self
        merged, n_rows, n_batches, drops = token
        if (len(self._delta) != n_batches
                or sum(b.shape[0] for b in self._delta) != n_rows):
            raise RuntimeError(
                "delta changed between prepare_compact and commit_compact; "
                "serialize writers around the prepare/commit pair")
        if frozenset(self._tombstones) != drops:
            raise RuntimeError(
                "tombstones changed between prepare_compact and "
                "commit_compact; serialize writers around the "
                "prepare/commit pair")
        self._idx = merged
        self._n_base = int(jnp.sum(merged.valid))
        self._delta = []
        self._delta_cat = None
        self._tombstones = set()
        self._first_tombstone_at = None
        self._delta_id0 = self._next_id
        self._masked = None
        self._masked_key = None
        self._lifecycle_ver += 1
        if self._mesh is not None:
            mesh, axis = self._mesh, self._mesh_axis
            self._mesh = None
            self.shard(mesh, axis=axis)
        return self

    # ------------------------------------------------------------------ #
    # sharding
    # ------------------------------------------------------------------ #
    def shard(self, mesh, axis: str = "data") -> "FreshIndex":
        """Block-shard the leaves (and their entries) over the `axis`
        axis of `mesh`, padding to a whole number of leaves per device,
        and route subsequent search() calls through the sharded
        expeditive/standard path.  Returns self.

        Concurrency: a writer (replaces the placed arrays and drops the
        compiled-search cache); serialize like add/compact.  A serving
        engine re-places through recover(), never this method directly.
        """
        n_dev = mesh.shape[axis]
        self._idx = shard_index(pad_leaves(self._idx, n_dev), mesh, axis=axis)
        self._mesh = mesh
        self._mesh_axis = axis
        self._sharded_fns = {}
        # the masked search view wraps the (now stale) placement
        self._masked = None
        self._masked_key = None
        self._lifecycle_ver += 1
        return self

    # ------------------------------------------------------------------ #
    # persistence
    # ------------------------------------------------------------------ #
    def save(self, directory: str, step: int = 0) -> str:
        """Persist config + index arrays (+ any pending delta) into
        `directory` at checkpoint `step`.  Returns the checkpoint path;
        restore with load() (new object) or reload() (in place), no
        rebuild.

        Concurrency: a reader of the index state; serialize against
        writers for a consistent cut (the engine's writer lock, or
        quiesce adds).
        """
        L = self.series_len
        delta = (np.concatenate(self._delta, axis=0) if self._delta
                 else np.zeros((0, L), np.float32))
        tree = {"index": self._idx._asdict(), "delta": delta}
        # TTL deadlines are monotonic-clock absolutes, meaningless in
        # another process: persist REMAINING seconds and re-anchor on
        # load (a restart therefore extends a TTL by at most the
        # downtime — the conservative direction: nothing expires early).
        now = time.monotonic()
        extra = {"config": self.config.to_dict(),
                 "n_series": self._n_base,
                 "format": "fresh-index-v1",
                 "lifecycle": {
                     "next_id": self._next_id,
                     "delta_id0": self._delta_id0,
                     "tombstones": sorted(self._tombstones),
                     "ttl": [[int(sid), max(0.0, dl - now)]
                             for sid, dl in sorted(self._ttl.items())],
                     "aliases": [[int(i), int(s)]
                                 for i, s in sorted(self._alias.items())],
                 }}
        if self._calibration is not None:
            extra["quality_calibration"] = self._calibration.to_dict()
        if self._autotune is not None:
            extra["autotune"] = self._autotune.to_dict()
        return save_checkpoint(directory, step, tree, extra=extra)

    @classmethod
    def load(cls, directory: str, step: Optional[int] = None) -> "FreshIndex":
        """Restore a save()d index from `directory` at `step` (None =
        latest): config + arrays, no rebuild.  The restored index is
        unsharded; call shard(mesh) to re-place it.

        Raises:
            ValueError: not a FreshIndex checkpoint, or the manifest's
                series count disagrees with the arrays (corruption).

        Concurrency: pure construction of a fresh object.
        """
        arrays, manifest = load_arrays(directory, step=step)
        extra = manifest.get("extra", {})
        if extra.get("format") != "fresh-index-v1":
            raise ValueError(
                f"{directory} is not a FreshIndex checkpoint "
                f"(format={extra.get('format')!r}); use "
                f"repro.checkpoint.load_checkpoint for raw pytrees")
        cfg = IndexConfig.from_dict(extra["config"])
        fields = FlatIndex._fields
        idx = FlatIndex(**{f: jnp.asarray(arrays[f"index/{f}"])
                           for f in fields})
        out = cls(idx, cfg)
        saved_n = extra.get("n_series")
        if saved_n is not None and saved_n != out._n_base:
            raise ValueError(
                f"corrupt checkpoint: manifest records {saved_n} series "
                f"but the index arrays hold {out._n_base}")
        delta = arrays.get("delta")
        if delta is not None and delta.shape[0]:
            out._delta = [np.asarray(delta, np.float32)]
        life = extra.get("lifecycle")
        if life is not None:
            now = time.monotonic()
            out._next_id = int(life["next_id"])
            out._delta_id0 = int(life["delta_id0"])
            out._tombstones = {int(t) for t in life["tombstones"]}
            out._ttl = {int(s): now + float(r) for s, r in life["ttl"]}
            out._alias = {int(i): int(s)
                          for i, s in life.get("aliases", ())}
            out._id_map = {s: i for i, s in out._alias.items()}
            if out._tombstones:
                # age restarts at load: conservative (drops no later
                # than staleness_budget_s after the restart)
                out._first_tombstone_at = now
        else:
            # pre-lifecycle checkpoint: ids were contiguous
            out._next_id = out._n_base + out.n_pending
            out._delta_id0 = out._n_base
        calib = extra.get("quality_calibration")
        if calib is not None:
            out._calibration = CalibrationTable.from_dict(calib)
        tuned = extra.get("autotune")
        if tuned is not None:
            from repro.kernels.autotune import AutotuneTable
            out._autotune = AutotuneTable.from_dict(tuned)
        return out

    def reload(self, directory: str, step: Optional[int] = None
               ) -> "FreshIndex":
        """Swap THIS object's arrays for a save()d checkpoint, in place.

        The elastic-recovery primitive: a serving engine holds one
        `FreshIndex` for its whole lifetime, so recovering a lost shard
        must restore arrays into the existing object rather than build a
        new one (`QueryEngine.recover` routes here).  The restored state
        is exactly `FreshIndex.load(directory, step)`: core arrays, any
        checkpointed delta, unsharded — call `shard(mesh)` afterwards to
        re-place it.

        Args:
            directory: checkpoint directory written by `save()`.
            step: checkpoint step to restore (None = latest).
        Returns:
            self, restored and unsharded.
        Raises:
            ValueError: not a FreshIndex checkpoint, or its IndexConfig
                disagrees with this index's (a checkpoint from a different
                config would silently change search semantics mid-serve).

        Concurrency: NOT safe against concurrent readers of this object;
        callers must serialize it like any other writer (the engine takes
        its writer lock and republishes an epoch around it).
        """
        loaded = FreshIndex.load(directory, step=step)
        if loaded.config != self.config:
            raise ValueError(
                f"checkpoint config {loaded.config} does not match this "
                f"index's {self.config}; refusing to reload across "
                f"configs")
        self._idx = loaded._idx
        self._n_base = loaded._n_base
        self._delta = loaded._delta
        self._delta_cat = None
        self._mesh = None
        self._sharded_fns = {}
        self._next_id = loaded._next_id
        self._delta_id0 = loaded._delta_id0
        self._tombstones = loaded._tombstones
        self._ttl = loaded._ttl
        self._first_tombstone_at = loaded._first_tombstone_at
        self._id_map = loaded._id_map
        self._alias = loaded._alias
        self._calibration = loaded._calibration
        self._autotune = loaded._autotune
        self._masked = None
        self._masked_key = None
        self._fp = None
        self._fp_key = None
        self._lifecycle_ver += 1
        return self

