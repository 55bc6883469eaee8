"""PlanCache: AOT-compiled search executables for steady-state serving.

The facade's `FreshIndex.search` leans on `jax.jit`'s trace cache: every
new (Q, k) shape pays a fresh trace + compile *inline on the caller*.  A
serving loop cannot afford that — the whole point of micro-batching into
a fixed set of shape buckets is that the executable for every bucket can
be built ONCE (`jax.jit(...).lower(...).compile()`) and steady-state
dispatch becomes a pure execute: no tracing, no cache probing beyond one
dict lookup here, hit/miss counters to prove it (tests/test_serve.py
asserts zero re-traces after warmup).

Plans are keyed on (bucket_Q, k, knobs, snapshot signature).  The
snapshot signature covers every static property of the compiled program:
core array shapes + storage dtype, the delta row count, n_base (the
delta id offset is baked in as a static), and whether the delta carries
a tombstone alive-mask (core tombstones mask the arrays, not the
program, so they need no signature bit).  Publishing a new epoch
(add/compact) therefore compiles at most once per (bucket, k) for that
epoch's shape — and an add-then-compact cycle that returns to a previous
shape reuses the old executable with the new arrays, because the arrays
are runtime arguments.

Sharded snapshots (the index lives on a mesh) compile through
`build_sharded_plan` instead: one shard_map executable per (bucket, k,
knobs, mesh placement) — the mesh's `runtime.sharding.mesh_sig` is part
of the snapshot signature, so an elastic re-mesh can never alias a stale
plan — plus, for delta-carrying epochs, one compiled `merge_delta_topk`
that folds the exact delta scan into the core answer.  That two-program
split is exactly what the sharded `FreshIndex.search` executes, which is
what keeps sharded serving bit-identical to the facade.

Donation: with `donate=True` the padded query batch is donated to XLA so
the hot path reuses its buffer for outputs (the batcher builds a fresh
device array per dispatch anyway).  Default is auto: on for tpu/gpu, off
for cpu — where XLA does not implement donation AND where reusing the
exact jitted `search_plan` / `snapshot_search` objects the facade calls
keeps engine results bit-identical to `FreshIndex.search` by
construction (same compiled program).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.search import (build_sharded_plan, merge_delta_topk,
                               search_plan, search_plan_impl,
                               snapshot_search, snapshot_search_impl)
from repro.runtime.sharding import mesh_sig

_PLAN_STATICS = ("k", "round_leaves", "znorm", "max_rounds", "backend",
                 "pq_budget", "stop_eps", "stop_leaves",
                 "dma_depth")
_SNAP_STATICS = _PLAN_STATICS + ("n_base",)


@dataclasses.dataclass(frozen=True)
class Knobs:
    """The fully-resolved search knobs one batch serves with (the exact
    tier's Knobs are resolved once at engine construction from
    EngineConfig -> IndexConfig; approx tiers get a twin with the
    stop-rule fields filled in from the calibration table).
    `sync_every` only affects sharded plans (the expeditive/standard
    all-reduce cadence); local plans ignore it.  `stop_eps` /
    `stop_leaves` are the approximate-search early-termination knobs
    (repro.quality.StopRule.lower()); their defaults compile the exact
    program.  `dma_depth` is the autotune-resolved kernel knob (Mosaic
    DMA ring depth): resolved
    from the index's AutotuneTable at engine construction, so a retuned
    table changes this dataclass and therefore — via `plan_key` — can
    never alias a stale AOT plan or result-cache entry."""
    round_leaves: int = 8
    znorm: bool = True
    max_rounds: Optional[int] = None
    backend: str = "ref"
    pq_budget: Optional[int] = None
    sync_every: int = 1
    stop_eps: float = 0.0
    stop_leaves: Optional[int] = None
    dma_depth: int = 1


def plan_key(k: int, knobs: Knobs) -> tuple:
    """EVERY search-semantics knob of a (k, knobs) request as one flat
    tuple — the single key-derivation helper both caches build on.
    `ResultCache` keys are `(fingerprint, epoch) + plan_key(...)` and
    `PlanCache` keys are `(bucket_q, snapshot_sig) + plan_key(...)`, so
    a knob added to `Knobs` (say a new stop rule field) automatically
    keys BOTH caches — exact and approx results/plans can never alias,
    and no call site can forget a field (tests assert the key length
    tracks `dataclasses.fields(Knobs)`)."""
    return (int(k),) + dataclasses.astuple(knobs)


class CompiledPlan:
    """One AOT-compiled executable: fixed (bucket_Q, k, knobs, snapshot
    shape).  `run(snapshot, queries)` -> (dist (Q, k), ids (Q, k),
    counts): the plan's (4,) counter array (`core.search.COUNTERS`).

    `has_alive` mirrors the snapshot's tombstone state: epochs whose
    delta carries an alive mask compile (and run) the masked program —
    the maskless one stays cached for mask-free epochs.  Core-row
    tombstones never appear here: they are masked in the ARRAYS
    (sentinel norms), not the program."""

    __slots__ = ("_exe", "has_delta", "has_alive", "bucket_q", "k", "calls")

    def __init__(self, exe, has_delta: bool, has_alive: bool,
                 bucket_q: int, k: int):
        self._exe = exe
        self.has_delta = has_delta
        self.has_alive = has_alive
        self.bucket_q = bucket_q
        self.k = k
        self.calls = 0

    def as_text(self) -> str:
        """The compiled program's HLO text (e.g. to check that a Mosaic
        kernel, `tpu_custom_call`, is in the served program)."""
        return self._exe.as_text()

    def run(self, snapshot, queries: jnp.ndarray):
        self.calls += 1
        if self.has_alive:
            return self._exe(snapshot.core, snapshot.delta, queries,
                             snapshot.delta_alive)
        if self.has_delta:
            return self._exe(snapshot.core, snapshot.delta, queries)
        return self._exe(snapshot.core, queries)


class ShardedCompiledPlan:
    """One AOT-compiled MESH executable pair for a sharded snapshot.

    `core` is the compiled `build_sharded_plan` program (shard_map over
    the mesh; returns (Q, k) dist/ids plus the replicated round count,
    a scalar: the sharded plan counts nothing else);
    `merge` (present only for delta-carrying epochs) is the compiled
    `merge_delta_topk` that folds the exact scan of the snapshot's delta
    into the core answer — the SAME two-program split the sharded facade
    path executes, so `submit().result()` stays bit-identical to
    `FreshIndex.search` on the sharded index."""

    __slots__ = ("_core", "_merge", "has_delta", "has_alive", "bucket_q",
                 "k", "calls")

    def __init__(self, core, merge, has_alive: bool, bucket_q: int, k: int):
        self._core = core
        self._merge = merge
        self.has_delta = merge is not None
        self.has_alive = has_alive
        self.bucket_q = bucket_q
        self.k = k
        self.calls = 0

    def as_text(self) -> str:
        """HLO text of the core program, then the merge program if any."""
        parts = [self._core.as_text()]
        if self._merge is not None:
            parts.append(self._merge.as_text())
        return "\n".join(parts)

    def run(self, snapshot, queries: jnp.ndarray):
        self.calls += 1
        d, i, rounds = self._core(snapshot.core, queries)
        if self._merge is not None:
            if self.has_alive:
                d, i = self._merge(snapshot.delta, queries, d, i,
                                   snapshot.delta_alive)
            else:
                d, i = self._merge(snapshot.delta, queries, d, i)
        return d, i, rounds


class PlanCache:
    """(bucket_Q, k, knobs, snapshot_sig) -> CompiledPlan, with counters."""

    def __init__(self, donate: Optional[bool] = None):
        if donate is None:
            donate = jax.default_backend() not in ("cpu",)
        self.donate = bool(donate)
        self.hits = 0
        self.misses = 0
        self._plans: Dict[Tuple, CompiledPlan] = {}
        self._donating_jits: Dict[bool, object] = {}
        self._sharded_jits: Dict[Tuple, object] = {}
        self._lock = threading.Lock()

    # ------------------------------------------------------------------ #
    def _jitted(self, has_delta: bool):
        """The jit object plans lower through.  Non-donating plans reuse
        the exact module-level jits the facade dispatches through — same
        program, bit-identical results; donating plans get a twin jit of
        the same pure impl with the query buffer donated."""
        if not self.donate:
            return snapshot_search if has_delta else search_plan
        fn = self._donating_jits.get(has_delta)
        if fn is None:
            if has_delta:
                fn = jax.jit(snapshot_search_impl,
                             static_argnames=_SNAP_STATICS,
                             donate_argnums=(2,))
            else:
                fn = jax.jit(search_plan_impl,
                             static_argnames=_PLAN_STATICS,
                             donate_argnums=(1,))
            self._donating_jits[has_delta] = fn
        return fn

    def get(self, snapshot, bucket_q: int, k: int,
            knobs: Knobs) -> CompiledPlan:
        """The compiled executable for this bucket, compiling on miss."""
        key = (bucket_q, snapshot.plan_sig) + plan_key(k, knobs)
        with self._lock:
            plan = self._plans.get(key)
            if plan is not None:
                self.hits += 1
                return plan
        plan = self._compile(snapshot, bucket_q, k, knobs)
        with self._lock:
            # two threads may race-compile the same key; keep the first
            # so CompiledPlan.calls stays meaningful, count one miss each
            self.misses += 1
            return self._plans.setdefault(key, plan)

    def _sharded_jit(self, snapshot, k: int, knobs: Knobs):
        """The jitted sharded plan for this (mesh placement, k, knobs).

        One jit object per key so every bucket of the same mesh lowers
        from the same traced function; the per-bucket executables are
        cached in `_plans` like local ones.  Sharded plans never donate —
        the query buffer is replicated over the mesh and a journal helper
        must be able to re-execute a batch from its host copy."""
        key = (mesh_sig(snapshot.mesh),
               snapshot.mesh_axis) + plan_key(k, knobs)
        with self._lock:
            # under the cache lock (jit-object creation is cheap — no
            # trace happens until .lower) so racing bucket compiles for
            # the same key share one traced function and the
            # sharded_traces counter stays truthful
            fn = self._sharded_jits.get(key)
            if fn is None:
                fn = jax.jit(build_sharded_plan(
                    snapshot.mesh, axis=snapshot.mesh_axis, k=k,
                    round_leaves=knobs.round_leaves,
                    sync_every=knobs.sync_every,
                    max_rounds=knobs.max_rounds,
                    znorm=knobs.znorm, backend=knobs.backend,
                    pq_budget=knobs.pq_budget,
                    stop_eps=knobs.stop_eps,
                    stop_leaves=knobs.stop_leaves,
                    dma_depth=knobs.dma_depth))
                self._sharded_jits[key] = fn
            return fn

    def _compile(self, snapshot, bucket_q: int, k: int,
                 knobs: Knobs) -> CompiledPlan:
        qs = jax.ShapeDtypeStruct((bucket_q, snapshot.series_len),
                                  jnp.float32)
        has_alive = getattr(snapshot, "delta_alive", None) is not None
        if snapshot.mesh is not None:
            core_exe = self._sharded_jit(snapshot, k, knobs).lower(
                snapshot.core, qs).compile()
            merge_exe = None
            if snapshot.delta is not None:
                # the core plan's (d, i) come out mesh-replicated; the
                # merge must be lowered for exactly that placement or the
                # AOT call rejects them (no auto-reshard on compiled exes)
                from jax.sharding import NamedSharding, PartitionSpec
                rep = NamedSharding(snapshot.mesh, PartitionSpec())
                ds = jax.ShapeDtypeStruct((bucket_q, k), jnp.float32,
                                          sharding=rep)
                is_ = jax.ShapeDtypeStruct((bucket_q, k), jnp.int32,
                                           sharding=rep)
                if has_alive:
                    merge_exe = merge_delta_topk.lower(
                        snapshot.delta, qs, ds, is_, snapshot.delta_alive,
                        k=k, n_base=snapshot.n_base,
                        znorm=knobs.znorm).compile()
                else:
                    merge_exe = merge_delta_topk.lower(
                        snapshot.delta, qs, ds, is_, k=k,
                        n_base=snapshot.n_base, znorm=knobs.znorm).compile()
            return ShardedCompiledPlan(core_exe, merge_exe, has_alive,
                                       bucket_q, k)
        kw = dict(k=k, round_leaves=knobs.round_leaves, znorm=knobs.znorm,
                  max_rounds=knobs.max_rounds, backend=knobs.backend,
                  pq_budget=knobs.pq_budget, stop_eps=knobs.stop_eps,
                  stop_leaves=knobs.stop_leaves,
                  dma_depth=knobs.dma_depth)
        has_delta = snapshot.delta is not None
        if has_alive:
            lowered = self._jitted(True).lower(
                snapshot.core, snapshot.delta, qs, snapshot.delta_alive,
                n_base=snapshot.n_base, **kw)
        elif has_delta:
            lowered = self._jitted(True).lower(
                snapshot.core, snapshot.delta, qs,
                n_base=snapshot.n_base, **kw)
        else:
            lowered = self._jitted(False).lower(snapshot.core, qs, **kw)
        return CompiledPlan(lowered.compile(), has_delta, has_alive,
                            bucket_q, k)

    def compiled(self) -> list:
        """The cached plans (CompiledPlan / ShardedCompiledPlan)."""
        with self._lock:
            return list(self._plans.values())

    # ------------------------------------------------------------------ #
    def stats(self) -> dict:
        """Counters proving (or disproving) steady-state zero-retrace:
        `misses` must freeze after warmup; `size` counts executables
        (sharded plan pairs count once); `sharded_traces` counts distinct
        (mesh, k, knobs) tracings behind those executables."""
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "size": len(self._plans), "donate": self.donate,
                    "sharded_traces": len(self._sharded_jits)}
