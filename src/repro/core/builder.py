"""IndexBuilder: the modular, Refresh-driven build pipeline (paper §IV-V).

The paper's headline contribution is *construction*: decompose the index
build into modular phases, then apply Refresh to every phase so the whole
build is lock-free.  `build_index` (core/index.py) is the opposite shape —
one fused device program.  This module is the paper-shaped API:

    builder = IndexBuilder(IndexConfig(...), workers=4)
    builder.feed(chunk_a)            # streaming ingest: summarize/key/sort
    builder.feed(chunk_b)            #   run eagerly as blocks fill
    index = builder.finalize()       # merge runs -> leaf stats -> FlatIndex

The build is an explicit phase graph, every phase split into PARTS driven
through a pluggable `core.traverse.Executor` — `SequentialExecutor` (the
single-shot oracle) or `RefreshExecutor` (lock-free multi-worker with
owner/helper modes, crash/delay injectors — Figures 7/8):

    summarize    per row-block: z-normalize -> PAA -> iSAX word -> ||x||^2
                 (jitted; backend='pallas' uses the fused summarize kernel)
    key          per row-block: round-robin bit-interleaved sort key
                 (numpy mirror of isax.interleaved_key — host-side exact)
    sort         per row-block: stable lexsort -> one sorted RUN per block
    merge        log2 levels of pairwise stable run merges (adjacent runs
                 only, so stability == one global stable sort)
    leaf_stats   per leaf-group: min/max boxes + the configured bound's
                 regions (the same `leaf_stats_blocks` the fused path jits)
    materialize  per row-block: gather series/summaries into the padded,
                 leaf-ordered FlatIndex arrays

Determinism is the core property: part boundaries depend only on
`part_rows` (never on feed boundaries), every payload writes deterministic
values into disjoint output slots, and helpers re-applying a part rewrite
the same bytes.  Therefore a 4-worker build under crash injectors is
BIT-IDENTICAL to the sequential single-shot build, and feeding N chunks is
bit-identical to feeding their concatenation (tests/test_builder.py).
Completion is guaranteed even if every worker crashes: phase driving goes
through `traverse_complete`, where the calling thread helps any part whose
done flag never set.

`merge_sorted_delta` is the incremental-compaction primitive built from
the same phases (Jiffy's batch merge, arXiv:2102.01044): the stored core
arrays are consumed AS-IS — series/paa/words/sq_norms bit-preserved, no
host reconstruction, no re-normalization, no re-rounding through float32
for half-precision storage — only the delta is summarized (once) and cast
to the storage dtype (once), then the two sorted runs merge stably.
`FreshIndex.compact()` and the serving engine's compaction both route
through it.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import isax
from .index import FlatIndex, leaf_stats_blocks
from .refresh import Injectors, RefreshExecutor
from .traverse import Executor, SequentialExecutor, traverse_complete

PHASES = ("summarize", "key", "sort", "merge", "leaf_stats", "materialize")


@functools.partial(jax.jit, static_argnames=("segments", "bits", "znorm"))
def _summarize_block_ref(raw, *, segments: int, bits: int, znorm: bool):
    """One summarize part (pure jnp): mirrors build_index's first stage."""
    x = isax.znormalize(raw) if znorm else raw
    x = x.astype(jnp.float32)
    p, w = isax.summarize(x, segments, bits)
    return x, p, w, jnp.sum(x * x, axis=-1)


def _summarize_block_pallas(raw, *, segments: int, bits: int, znorm: bool):
    """One summarize part through the fused Pallas kernel."""
    from repro.kernels import ops
    x = jnp.asarray(raw)
    # normalized in the kernel, exactly like the fused build_index
    p, w = ops.summarize(x, segments=segments, bits=bits, znorm=znorm)
    w = w.astype(jnp.uint8 if bits <= 8 else jnp.int32)
    x = isax.znormalize(x) if znorm else x
    x = x.astype(jnp.float32)
    return x, p, w, jnp.sum(x * x, axis=-1)


_leaf_stats_jit = functools.partial(
    jax.jit, static_argnames=("bits", "bound"))(leaf_stats_blocks)


def _cat(blocks: List[np.ndarray]) -> np.ndarray:
    return blocks[0] if len(blocks) == 1 else np.concatenate(blocks, axis=0)


def _merge_two_sorted(a_ids: np.ndarray, b_ids: np.ndarray,
                      a_keys: np.ndarray, b_keys: np.ndarray) -> np.ndarray:
    """Stable linear merge of two sorted runs: binary-search each of b's
    packed keys into a (`side='right'` — a wins ties), then scatter both
    id lists into their merged slots.  O(m log n) + O(n + m) scatter; the
    stability contract (a's ids all precede b's on equal keys, both runs
    internally stable) is what composes to one global stable sort."""
    pos = np.searchsorted(a_keys, b_keys, side="right")
    out = np.empty(a_ids.shape[0] + b_ids.shape[0], np.int64)
    tgt_b = pos + np.arange(b_ids.shape[0])
    mask = np.ones(out.shape[0], bool)
    mask[tgt_b] = False
    out[mask] = a_ids
    out[tgt_b] = b_ids
    return out


def _finalize_from_order(series_src: np.ndarray, paa: np.ndarray,
                         words: np.ndarray, sqn: np.ndarray,
                         order: np.ndarray, perm_src: Optional[np.ndarray],
                         config, run_phase: Callable[[str, int, Callable],
                                                     None],
                         part_rows: int) -> FlatIndex:
    """leaf_stats + materialize phases over an already-merged global order.

    series_src/paa/words/sqn are SOURCE-ordered; `order` maps sorted
    position -> source row; `perm_src` maps source row -> original series
    id (None = source row IS the original id, the fresh-build case).
    Shared by `IndexBuilder.finalize` and `merge_sorted_delta` so a
    compacted index and a fresh build cannot drift.
    """
    n = order.shape[0]
    M = config.leaf_capacity
    w = paa.shape[1]
    L = series_src.shape[1]
    maxsym = (1 << config.bits) - 1
    n_pad = -(-n // M) * M
    n_leaves = n_pad // M

    out_series = np.zeros((n_pad, L), dtype=series_src.dtype)
    out_paa = np.full((n_pad, w), np.inf, np.float32)
    out_words = np.full((n_pad, w), maxsym, words.dtype)
    out_sqn = np.full((n_pad,), 1e30, np.float32)
    out_perm = np.full((n_pad,), -1, np.int32)
    leaf_lo = np.empty((n_leaves, w), np.float32)
    leaf_hi = np.empty((n_leaves, w), np.float32)
    leaf_valid = np.empty((n_leaves,), bool)

    # ---- per-leaf stats: parts are groups of whole leaves ----------------
    leaves_per_part = max(1, part_rows // M)
    n_lparts = -(-n_leaves // leaves_per_part)

    def p_leaf_stats(i: int) -> None:
        gl = i * leaves_per_part
        gh = min(gl + leaves_per_part, n_leaves)
        g = gh - gl
        rlo = gl * M
        m_exist = max(0, min(gh * M, n) - rlo)
        pw = np.full((g * M, w), np.inf, np.float32)
        ww = np.full((g * M, w), maxsym, words.dtype)
        vm = np.zeros((g * M,), bool)
        if m_exist:
            rows = order[rlo:rlo + m_exist]
            pw[:m_exist] = paa[rows]
            ww[:m_exist] = words[rows]
            vm[:m_exist] = True
        lo, hi, lv = _leaf_stats_jit(
            jnp.asarray(pw.reshape(g, M, w)),
            jnp.asarray(ww.reshape(g, M, w)),
            jnp.asarray(vm.reshape(g, M, 1)),
            bits=config.bits, bound=config.bound)
        leaf_lo[gl:gh] = np.asarray(lo)
        leaf_hi[gl:gh] = np.asarray(hi)
        leaf_valid[gl:gh] = np.asarray(lv)

    run_phase("leaf_stats", n_lparts, p_leaf_stats)

    # ---- materialize: gather rows into the padded leaf-ordered arrays ----
    n_mparts = -(-n_pad // part_rows)

    def p_materialize(i: int) -> None:
        lo = i * part_rows
        m_exist = max(0, min(lo + part_rows, n) - lo)
        if not m_exist:
            return                      # pure padding rows: prefilled
        rows = order[lo:lo + m_exist]
        out_series[lo:lo + m_exist] = series_src[rows]
        out_paa[lo:lo + m_exist] = paa[rows]
        out_words[lo:lo + m_exist] = words[rows]
        out_sqn[lo:lo + m_exist] = sqn[rows]
        out_perm[lo:lo + m_exist] = (
            rows.astype(np.int32) if perm_src is None else perm_src[rows])

    run_phase("materialize", n_mparts, p_materialize)

    return FlatIndex(series=jnp.asarray(out_series),
                     paa=jnp.asarray(out_paa),
                     words=jnp.asarray(out_words),
                     sq_norms=jnp.asarray(out_sqn),
                     perm=jnp.asarray(out_perm),
                     valid=jnp.asarray(out_perm >= 0),
                     leaf_lo=jnp.asarray(leaf_lo),
                     leaf_hi=jnp.asarray(leaf_hi),
                     leaf_valid=jnp.asarray(leaf_valid))


class IndexBuilder:
    """Streaming, phase-modular, lock-free index construction.

    config     IndexConfig (or None for defaults); `**overrides` are
               IndexConfig fields, mirroring `FreshIndex.build`
    workers    0/1 = sequential single-shot; N >= 2 = RefreshExecutor with
               N lock-free workers (owner/helper modes per phase)
    part_rows  rows per part — the unit of work assignment.  Part
               boundaries depend ONLY on this value, never on how feed()
               calls sliced the data, which is what makes chunked feeds
               bit-identical to one-shot builds
    injectors  refresh.Injectors for crash/delay experiments (multi-worker
               only); even with every worker crashed, finalize() completes
               because the calling thread helps (traverse_complete)
    executor   explicit traverse.Executor (overrides workers/injectors)
    """

    def __init__(self, config=None, *, workers: int = 0,
                 part_rows: int = 2048,
                 injectors: Optional[Injectors] = None,
                 executor: Optional[Executor] = None, **overrides):
        if config is None:
            from repro.api import IndexConfig
            config = IndexConfig()
        if overrides:
            config = dataclasses.replace(config, **overrides)
        self.config = config
        if part_rows < 1:
            raise ValueError("part_rows must be >= 1")
        self.part_rows = int(part_rows)
        self.workers = int(workers)
        if executor is not None:
            self._executor = executor
        elif self.workers >= 2:
            self._executor = RefreshExecutor(n_threads=self.workers,
                                             injectors=injectors)
        else:
            self._executor = SequentialExecutor()

        self._L: Optional[int] = None
        self._n = 0
        self._tail: List[np.ndarray] = []      # fed rows not yet a block
        self._tail_rows = 0
        self._raw_blocks: List[np.ndarray] = []
        self._offsets: List[int] = []          # global row offset per block
        self._xn: List[np.ndarray] = []        # f32 normalized series
        self._paa: List[np.ndarray] = []
        self._words: List[np.ndarray] = []
        self._sqn: List[np.ndarray] = []
        self._keys: List[np.ndarray] = []
        self._runs: List[np.ndarray] = []      # sorted global ids per block
        self._finalized = False
        self._stats = {p: {"parts": 0, "runs": 0, "applications": 0,
                           "helped_parts": 0, "mode_switches": 0,
                           "crashed_workers": 0, "wall_time": 0.0}
                       for p in PHASES}

    # ------------------------------------------------------------------ #
    # public API
    # ------------------------------------------------------------------ #
    @property
    def n_fed(self) -> int:
        """Total rows fed so far (processed blocks + buffered tail)."""
        return self._n + self._tail_rows

    def feed(self, chunk) -> "IndexBuilder":
        """Ingest `chunk`, an (m, L) or (L,) series array; returns self.
        Complete `part_rows`-sized blocks are summarized/keyed/sorted
        EAGERLY (streaming build); the remainder buffers until the next
        feed or finalize().

        Raises:
            ValueError: chunk is not 1/2-D or its series length
                disagrees with earlier feeds (or the config).
            RuntimeError: called after finalize().

        Concurrency: single feeder — call from one thread; the phase
        work itself fans out to the lock-free Refresh workers, and the
        caller's chunk buffer may be reused after feed() returns (the
        builder copies what outlives the call).
        """
        if self._finalized:
            raise RuntimeError("feed() after finalize()")
        c = np.asarray(chunk, np.float32)
        if c.ndim == 1:
            c = c[None]
        if c.ndim != 2:
            raise ValueError(f"chunk must be (m, L), got shape {c.shape}")
        if self._L is None:
            self.config.validate_series_len(c.shape[1])
            self._L = c.shape[1]
        elif c.shape[1] != self._L:
            raise ValueError(f"chunk has series length {c.shape[1]}, "
                             f"builder holds length {self._L}")
        if c.shape[0] == 0:
            return self
        self._tail.append(c)
        self._tail_rows += c.shape[0]
        blocks = []
        while self._tail_rows >= self.part_rows:
            blocks.append(self._take_rows(self.part_rows))
        if blocks:
            self._process_blocks(blocks)
        # complete blocks were consumed above, inside this call; whatever
        # stays in the tail outlives it, so the builder must own it —
        # callers may legitimately reuse their chunk buffer between feeds
        # (the read-into-buffer streaming pattern).  Only the LAST entry
        # can alias this call's chunk (earlier entries are prior feeds'
        # copies; block-cutting consumes from the front).
        if self._tail and np.shares_memory(self._tail[-1], c):
            self._tail[-1] = self._tail[-1].copy()
        return self

    def finalize(self):
        """Run the remaining phases and return the finished FreshIndex.

        Flushes the ragged tail block, merges the per-block sorted runs
        (log2 pairwise levels), computes per-leaf stats and materializes
        the FlatIndex — every phase through the configured executor.

        Raises:
            RuntimeError: finalize() was already called (single-use).
            ValueError: nothing was ever fed (series length unknown).

        Concurrency: single caller; completes even if every Refresh
        worker crashed — the calling thread helps unfinished parts
        (traverse_complete), the paper's termination guarantee.
        """
        if self._finalized:
            raise RuntimeError("finalize() already called")
        order, xn, paa, words, sqn, _ = self._sorted_run()
        flat = _finalize_from_order(
            self._cast_series(xn), paa, words, sqn,
            order, None, self.config, self._run_phase, self.part_rows)
        self._finalized = True
        from repro.api import FreshIndex
        return FreshIndex(flat, self.config)

    def report(self) -> dict:
        """Per-phase build telemetry: parts, payload applications (>=
        parts under helping), helped parts, crashes, wall time.

        Concurrency: read-only; between phases the counters are a
        consistent cut, mid-phase reads may lag the workers.
        """
        return {"n_rows": self.n_fed, "part_rows": self.part_rows,
                "workers": self.workers,
                "phases": {p: dict(s) for p, s in self._stats.items()}}

    # ------------------------------------------------------------------ #
    # internals
    # ------------------------------------------------------------------ #
    def _run_phase(self, name: str, n_parts: int, payload) -> None:
        if n_parts == 0:
            return
        stats = traverse_complete(self._executor, n_parts, payload)
        rec = self._stats[name]
        rec["parts"] += n_parts
        rec["runs"] += 1
        if stats is not None:
            rec["applications"] += stats.applications
            rec["helped_parts"] += stats.helped_parts
            rec["mode_switches"] += stats.mode_switches
            rec["crashed_workers"] += stats.crashed_workers
            rec["wall_time"] += stats.wall_time

    def _sorted_run(self):
        """Flush the tail, merge the runs, and hand back the globally
        sorted view: (order, xn, paa, words, sqn, keys) with order
        mapping sorted position -> fed row.  The one seam `finalize` and
        `merge_sorted_delta` share; consumes the per-block buffers (they
        are released here — a builder is single-use)."""
        if self._tail_rows:
            self._process_blocks([self._take_rows(self._tail_rows)])
        if self._n == 0:
            if self._L is None:
                raise ValueError("no data fed; call feed() before "
                                 "finalize()")
            # an EMPTY build is legal once the series length is known
            # (feed of a (0, L) chunk): the bootstrap pattern
            # build(empty) -> add() -> compact()
            cfg = self.config
            wdt = np.uint8 if cfg.bits <= 8 else np.int32
            lanes = -(-cfg.segments * cfg.bits // 31)
            return (np.empty(0, np.int64),
                    np.empty((0, self._L), np.float32),
                    np.empty((0, cfg.segments), np.float32),
                    np.empty((0, cfg.segments), wdt),
                    np.empty(0, np.float32),
                    np.empty((0, lanes), np.int32))
        keys = _cat(self._keys)
        order = self._merge_runs(keys)
        out = (order, _cat(self._xn), _cat(self._paa), _cat(self._words),
               _cat(self._sqn), keys)
        # per-block intermediates are dead once concatenated; drop them so
        # peak host memory stays ~1x the dataset plus the output
        for lst in (self._xn, self._paa, self._words, self._sqn,
                    self._keys, self._runs):
            lst.clear()
        return out

    def _take_rows(self, m: int) -> np.ndarray:
        out, got = [], 0
        while got < m:
            a = self._tail[0]
            need = m - got
            if a.shape[0] <= need:
                out.append(a)
                got += a.shape[0]
                self._tail.pop(0)
            else:
                out.append(a[:need])
                self._tail[0] = a[need:]
                got = m
        self._tail_rows -= m
        return out[0] if len(out) == 1 else np.concatenate(out, axis=0)

    def _summarize(self, raw: np.ndarray):
        cfg = self.config
        fn = (_summarize_block_pallas if cfg.backend == "pallas"
              else _summarize_block_ref)
        return fn(jnp.asarray(raw), segments=cfg.segments, bits=cfg.bits,
                  znorm=cfg.znorm)

    def _process_blocks(self, blocks: List[np.ndarray]) -> None:
        """Phases summarize -> key -> sort over newly completed blocks.

        Each payload writes one block's slot — disjoint, deterministic,
        idempotent, so any Refresh schedule (including helpers re-applying
        parts) produces the same bytes."""
        start = len(self._raw_blocks)
        for b in blocks:
            self._raw_blocks.append(b)
            self._offsets.append(self._n)
            self._n += b.shape[0]
            for lst in (self._xn, self._paa, self._words, self._sqn,
                        self._keys, self._runs):
                lst.append(None)
        nb = len(blocks)

        def p_summarize(i: int) -> None:
            j = start + i
            x, p, w, s = self._summarize(self._raw_blocks[j])
            self._xn[j] = np.asarray(x)
            self._paa[j] = np.asarray(p)
            self._words[j] = np.asarray(w)
            self._sqn[j] = np.asarray(s)
        self._run_phase("summarize", nb, p_summarize)
        # raw rows are dead after summarization; release them only once
        # the whole phase is done (helpers may re-apply parts within it)
        for i in range(nb):
            self._raw_blocks[start + i] = None

        def p_key(i: int) -> None:
            j = start + i
            self._keys[j] = isax.interleaved_key_np(self._words[j],
                                                    self.config.bits)
        self._run_phase("key", nb, p_key)

        def p_sort(i: int) -> None:
            j = start + i
            order = isax.lexsort_keys(self._keys[j])
            self._runs[j] = (self._offsets[j] + order).astype(np.int64)
        self._run_phase("sort", nb, p_sort)

    def _merge_runs(self, keys_cat: np.ndarray) -> np.ndarray:
        """Pairwise-merge adjacent sorted runs until one remains.

        Runs stay in ascending global-row order at every level, and each
        pairwise step is a true linear merge via `_merge_two_sorted`
        (left run wins key ties = lower original rows first), so the
        composition equals the one global stable lexsort the fused build
        performs — without ever re-sorting a run."""
        runs = list(self._runs)
        if len(runs) == 1:
            return runs[0]
        packed = isax.pack_keys_bytes(keys_cat)
        while len(runs) > 1:
            pairs = [(runs[i], runs[i + 1])
                     for i in range(0, len(runs) - 1, 2)]
            carry = [runs[-1]] if len(runs) % 2 else []
            nxt: List[Optional[np.ndarray]] = [None] * len(pairs)

            def p_merge(i: int) -> None:
                a, b = pairs[i]
                nxt[i] = _merge_two_sorted(a, b, packed[a], packed[b])
            self._run_phase("merge", len(pairs), p_merge)
            runs = nxt + carry
        return runs[0]

    def _cast_series(self, xn: np.ndarray) -> np.ndarray:
        dtype = self.config.dtype
        if dtype == "float32":
            return xn
        dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float16
        return np.asarray(jnp.asarray(xn).astype(dt))


def merge_sorted_delta(core: FlatIndex, delta, config, *,
                       drop_ids=None, delta_id0: Optional[int] = None,
                       workers: int = 0, part_rows: int = 2048,
                       injectors: Optional[Injectors] = None,
                       executor: Optional[Executor] = None) -> FlatIndex:
    """Incremental compaction: stable-merge the sorted core with a sorted
    delta run — the Jiffy-style batch merge `FreshIndex.compact()` uses.

    The stored core arrays are consumed AS-IS: series (whatever the
    storage dtype), paa, words, sq_norms and perm of the valid prefix are
    bit-preserved into the merged index, so repeated compacts never
    re-round half-precision storage through float32 and never re-normalize
    already-stored series.  Only the delta is normalized + summarized
    (once, in float32) and cast to the storage dtype (once).  With
    float32 storage the result is bit-identical to a fresh `IndexBuilder`
    build over the concatenated data; delta ids continue at the
    `delta_id0` offset (default: the core's valid row count — the
    historical contiguous-id behavior).

    `drop_ids` (iterable of series ids) is the PHYSICAL half of logical
    deletion: tombstoned core rows are filtered out of the merge input
    (removing a row from an already-sorted run keeps it sorted) and
    tombstoned delta rows never enter the delta run, so each dropped id
    disappears exactly once and the output arrays shrink by exactly the
    dropped count.  Ids are never reused, so compacting an already
    drop-free index with the same `drop_ids` is the identity —
    compact∘compact == compact holds with or without drops.
    """
    delta = np.asarray(delta, np.float32)
    if delta.ndim != 2:
        raise ValueError(f"delta must be (m, L), got shape {delta.shape}")
    drops = (np.unique(np.fromiter(drop_ids, np.int64))
             if drop_ids else np.empty(0, np.int64))
    if delta.shape[0] == 0 and drops.size == 0:
        return core

    perm_np = np.asarray(core.perm)
    valid_np = np.asarray(core.valid)
    n_base = int(valid_np.sum())
    if not bool(valid_np[:n_base].all()):
        raise ValueError("core index has non-trailing padding rows; "
                         "cannot merge incrementally")
    if delta_id0 is None:
        delta_id0 = n_base

    # ---- core run: the valid prefix minus tombstoned rows (a filtered
    # sorted run is still sorted) --------------------------------------
    core_perm = perm_np[:n_base].astype(np.int32)
    keep = (~np.isin(core_perm, drops) if drops.size
            else np.ones(n_base, bool))
    core_series = np.asarray(core.series)[:n_base][keep]
    core_paa = np.asarray(core.paa)[:n_base][keep]
    core_words = np.asarray(core.words)[:n_base][keep]
    core_sqn = np.asarray(core.sq_norms)[:n_base][keep]
    core_perm = core_perm[keep]
    n_core = int(keep.sum())

    # ---- delta rows: tombstoned ids never enter the run ---------------
    pos = np.arange(delta.shape[0], dtype=np.int64)
    dkeep = (~np.isin(delta_id0 + pos, drops) if drops.size
             else np.ones(delta.shape[0], bool))
    delta_kept = delta[dkeep]
    delta_ids = (delta_id0 + pos[dkeep]).astype(np.int32)

    b = IndexBuilder(config, workers=workers, part_rows=part_rows,
                     injectors=injectors, executor=executor)
    if delta_kept.shape[0] == 0:
        # Drops-only compaction: the filtered core is already in key
        # order, so re-finalize it directly (re-blocks leaves, re-pads).
        return _finalize_from_order(
            core_series, core_paa, core_words, core_sqn,
            np.arange(n_core, dtype=np.int64), core_perm, config,
            b._run_phase, b.part_rows)

    # ---- delta run: the builder's own summarize/key/sort/merge phases ----
    d_order, d_xn, d_paa, d_words, d_sqn, d_keys = \
        b.feed(delta_kept)._sorted_run()
    d_keys = d_keys[d_order]
    d_series = b._cast_series(d_xn)[d_order]
    d_paa = d_paa[d_order]
    d_words = d_words[d_order]
    d_sqn = d_sqn[d_order]

    # ---- core keys recomputed from the STORED words (exact ints) ----
    n_lanes = d_keys.shape[1]
    core_keys = np.empty((n_core, n_lanes), np.int32)
    n_kparts = -(-n_core // b.part_rows)

    def p_core_key(i: int) -> None:
        lo = i * b.part_rows
        hi = min(lo + b.part_rows, n_core)
        core_keys[lo:hi] = isax.interleaved_key_np(core_words[lo:hi],
                                                   config.bits)
    b._run_phase("key", n_kparts, p_core_key)

    # ---- one stable two-run merge: binary-search each sorted delta key
    # into the sorted core (side='right' -> core wins key ties, which
    # preserves the global original-id tie order: core ids < delta ids
    # because ids are monotone and delta_id0 follows every core id;
    # equal delta keys stay in fed order since d_order is stable).  This
    # is O(m log n) — no global re-sort of the core ever happens. --------
    out: dict = {}

    def p_merge(_: int) -> None:
        m = d_keys.shape[0]
        out["order"] = _merge_two_sorted(
            np.arange(n_core, dtype=np.int64),
            np.arange(n_core, n_core + m, dtype=np.int64),
            isax.pack_keys_bytes(core_keys), isax.pack_keys_bytes(d_keys))
    b._run_phase("merge", 1, p_merge)

    series_src = np.concatenate([core_series, d_series])
    paa_src = np.concatenate([core_paa, d_paa])
    words_src = np.concatenate([core_words, d_words])
    sqn_src = np.concatenate([core_sqn, d_sqn])
    perm_src = np.concatenate([core_perm, delta_ids[d_order]])

    return _finalize_from_order(series_src, paa_src, words_src, sqn_src,
                                out["order"], perm_src, config,
                                b._run_phase, b.part_rows)
