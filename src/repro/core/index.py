"""Device data plane: the flat, TPU-native FreSh index.

The paper's leaf-oriented fat-leaf tree is a pointer structure optimized for
shared-memory cores.  Pointer chasing is hostile to TPU vector units, so the
device-resident index *flattens* the tree (the same move the paper family's
GPU member, SING [11], makes):

  * every series is summarized (PAA + iSAX word — Pallas kernel);
  * series are sorted by the round-robin bit-interleaved iSAX key
    (isax.interleaved_key).  This order IS the leaf order of a balanced
    iSAX tree that splits segments round-robin one bit at a time, so
  * leaves = fixed-capacity blocks of M consecutive sorted entries, and the
    per-leaf summaries (common iSAX prefix per segment; min/max symbols;
    min/max PAA) are dense (n_leaves, w) arrays => pruning is one vectorized
    lower-bound kernel over all leaves instead of a tree walk.

Three lower bounds, all sound (tests prove the pruning property for each):
    'prefix' — the paper's MINDIST on the leaf's common iSAX prefix region
               (exactly what a tree node's key gives you).     [faithful]
    'symbox' — region spanned by per-leaf min/max symbols.     [>= prefix]
    'paabox' — per-leaf min/max raw PAA box.                   [tightest]

Locality (Definition IV.1) on the mesh: leaves are block-sharded over the
'data' axis, so every device owns its own leaves — disjoint data, zero
intra-stage communication, balanced by construction (equal block counts),
i.e. the three locality-aware principles survive the port.  What a
device's leaves cover depends on how the index was built: `shard_index`
over a `build_index` result gives each device a contiguous range of the
global key order, while `build_index_sharded` (a build over series
already row-sharded over the mesh) sorts each device's own rows alone,
so a device's leaves cover its rows only, in their own key order, and
the key ranges of different devices overlap.  The search is exact
either way: every device prunes with its own leaves' bounds.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import isax


class FlatIndex(NamedTuple):
    """Device-resident index (a pytree: shardable, checkpointable)."""
    series: jnp.ndarray        # (n_pad, L)  z-normalized, leaf order
    paa: jnp.ndarray           # (n_pad, w)
    words: jnp.ndarray         # (n_pad, w) uint8
    sq_norms: jnp.ndarray      # (n_pad,)   ||x||^2 (refinement epilogue)
    perm: jnp.ndarray          # (n_pad,)   original series id; -1 for padding
    valid: jnp.ndarray         # (n_pad,)   bool
    leaf_lo: jnp.ndarray       # (n_leaves, w) region lower edge (f32)
    leaf_hi: jnp.ndarray       # (n_leaves, w) region upper edge (f32)
    leaf_valid: jnp.ndarray    # (n_leaves,) bool (fully-padded leaves False)

    @property
    def leaf_capacity(self) -> int:
        return self.series.shape[0] // self.leaf_lo.shape[0]

    @property
    def n_leaves(self) -> int:
        return self.leaf_lo.shape[0]


def _bit_length_u8(x: jnp.ndarray) -> jnp.ndarray:
    """bit_length for uint8 values, elementwise."""
    x = x.astype(jnp.int32)
    return ((x > 0).astype(jnp.int32) + (x > 1) + (x > 3) + (x > 7)
            + (x > 15) + (x > 31) + (x > 63) + (x > 127))


def leaf_regions(lo_sym: jnp.ndarray, hi_sym: jnp.ndarray,
                 lo_paa: jnp.ndarray, hi_paa: jnp.ndarray,
                 bound: str = "prefix",
                 bits: int = isax.SAX_BITS):
    """Per-leaf per-segment [lo, hi] region for the chosen bound."""
    if bound == "paabox":
        return lo_paa, hi_paa
    if bound == "symbox":
        lo, _ = isax.symbol_region(lo_sym, bits, bits)
        _, hi = isax.symbol_region(hi_sym, bits, bits)
        return lo, hi
    if bound == "prefix":
        # common prefix depth per segment = bits - bit_length(lo XOR hi)
        depth = bits - _bit_length_u8(jnp.bitwise_xor(lo_sym, hi_sym))
        lo, hi = isax.symbol_region(lo_sym, depth, bits)
        return lo, hi
    raise ValueError(f"unknown bound {bound!r}")


def leaf_stats_blocks(pw: jnp.ndarray, ww: jnp.ndarray, vmask: jnp.ndarray,
                      *, bits: int, bound: str):
    """Per-leaf summaries from leaf-blocked sorted entries.

    pw: (n_leaves, M, w) PAA, ww: (n_leaves, M, w) symbols, vmask:
    (n_leaves, M, 1) validity.  Returns (leaf_lo, leaf_hi, leaf_valid)
    with fully-padded leaves carrying empty regions at +inf.  The one
    per-leaf-stats computation both the fused `build_index` program and
    `IndexBuilder`'s leaf_stats phase execute, so the two paths cannot
    drift."""
    big = jnp.asarray(jnp.inf, pw.dtype)
    lo_paa = jnp.min(jnp.where(vmask, pw, big), axis=1)
    hi_paa = jnp.max(jnp.where(vmask, pw, -big), axis=1)
    lo_sym = jnp.min(jnp.where(vmask, ww, (1 << bits) - 1),
                     axis=1).astype(jnp.uint8)
    hi_sym = jnp.max(jnp.where(vmask, ww, 0), axis=1).astype(jnp.uint8)
    leaf_valid = jnp.any(vmask[..., 0], axis=1)
    lo, hi = leaf_regions(lo_sym, hi_sym, lo_paa, hi_paa, bound, bits)
    lo = jnp.where(leaf_valid[:, None], lo, big)
    hi = jnp.where(leaf_valid[:, None], hi, big)
    return lo, hi, leaf_valid


@functools.partial(jax.jit, static_argnames=("segments", "bits",
                                             "leaf_capacity", "znorm",
                                             "bound", "backend"))
def build_index(raw: jnp.ndarray,
                *,
                segments: int = isax.SEGMENTS,
                bits: int = isax.SAX_BITS,
                leaf_capacity: int = 64,
                znorm: bool = True,
                bound: str = "prefix",
                backend: str = "ref") -> FlatIndex:
    """Bulk index construction as ONE fused device program.

    raw: (n, L) float series.  n is padded up to a leaf multiple.
    The global sort is the only step with cross-shard dataflow (an all-to-all
    under pjit) — everything else is embarrassingly local, mirroring the
    paper's "threads work on disjoint buffers/subtrees" design.  Series
    already row-sharded over a mesh build shard by shard instead
    (`build_index_sharded`), with no cross-shard dataflow at all.

    backend 'pallas' runs the summarization stage through the fused Pallas
    kernel (Mosaic on TPU, interpret elsewhere); 'ref' is pure jnp.

    This is the maximal-throughput single-shot path.  The SUPPORTED build
    API is `core.builder.IndexBuilder` (what `FreshIndex.build` uses): the
    same math decomposed into Refresh-driven phases, so builds stream,
    run on multiple lock-free workers, and merge incrementally — see the
    phase-equivalence tests in tests/test_builder.py proving the two
    paths produce bit-identical indexes.
    """
    return _build_body(raw, segments=segments, bits=bits,
                       leaf_capacity=leaf_capacity, znorm=znorm,
                       bound=bound, backend=backend)


def _build_body(raw: jnp.ndarray, *, segments: int, bits: int,
                leaf_capacity: int, znorm: bool, bound: str, backend: str,
                first_row=None) -> FlatIndex:
    """The build of one block of rows: what `build_index` runs over the
    whole array and `build_index_sharded` over each device's block.
    `first_row` (None: 0) is the global id of the block's first row; the
    ids in `perm` are offset by it."""
    n, L = raw.shape

    def prepare(r):
        r = isax.znormalize(r) if znorm else r
        return r.astype(jnp.float32)

    if backend == "pallas":
        from repro.kernels import ops
        # the kernel normalizes in VMEM: no normalized copy hits HBM
        p, w = ops.summarize(raw, segments=segments, bits=bits, znorm=znorm)
        w = w.astype(jnp.uint8 if bits <= 8 else jnp.int32)
    else:
        p, w = isax.summarize(prepare(raw), segments, bits)

    # ---- sort by interleaved key (leaf order of the round-robin tree) ----
    order = isax.lexsort_lanes(isax.interleaved_key(w, bits))
    # normalize AFTER the gather (row-wise, so the same values): the
    # gather and the normalization fuse into one pass writing the output
    x, p, w = prepare(raw[order]), p[order], w[order]
    perm = order if first_row is None else order + first_row

    # ---- pad to a whole number of leaves ---------------------------------
    n_pad = -(-n // leaf_capacity) * leaf_capacity
    pad = n_pad - n
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        # padded symbols = max symbol; padded PAA = +inf so boxes stay tight
        p = jnp.pad(p, ((0, pad), (0, 0)), constant_values=jnp.inf)
        w = jnp.pad(w, ((0, pad), (0, 0)), constant_values=(1 << bits) - 1)
        perm = jnp.pad(perm, (0, pad), constant_values=-1)
    valid = perm >= 0

    n_leaves = n_pad // leaf_capacity
    pw = p.reshape(n_leaves, leaf_capacity, segments)
    ww = w.reshape(n_leaves, leaf_capacity, segments)
    vmask = valid.reshape(n_leaves, leaf_capacity, 1)

    # fully-padded leaves: empty region at +inf so their lb is +inf
    lo, hi, leaf_valid = leaf_stats_blocks(pw, ww, vmask, bits=bits,
                                           bound=bound)

    sq_norms = jnp.sum(x * x, axis=-1)
    # padded rows must never win a min: push their norms (hence distances) up
    sq_norms = jnp.where(valid, sq_norms, 1e30)

    return FlatIndex(series=x, paa=p, words=w, sq_norms=sq_norms,
                     perm=perm, valid=valid,
                     leaf_lo=lo, leaf_hi=hi, leaf_valid=leaf_valid)


def rows_placement(data) -> Optional[Tuple[Mesh, str]]:
    """(mesh, axis) where `data` is a 2-D jax.Array whose rows are split
    evenly over one axis, of more than one device, of a mesh and whose
    columns are not split; None for any other array (a host array, one
    device, a replicated or column-split placement)."""
    sh = getattr(data, "sharding", None)
    if not isinstance(sh, NamedSharding) or getattr(data, "ndim", 0) != 2:
        return None
    spec = tuple(sh.spec) + (None, None)
    axis = spec[0]
    if isinstance(axis, tuple) and len(axis) == 1:
        axis = axis[0]
    if not isinstance(axis, str) or spec[1] is not None:
        return None
    n_dev = sh.mesh.shape[axis]
    if n_dev < 2 or data.shape[0] % n_dev:
        return None
    return sh.mesh, axis


def build_index_sharded(raw: jnp.ndarray, mesh: Mesh, axis: str = "data",
                        *, segments: int = isax.SEGMENTS,
                        bits: int = isax.SAX_BITS, leaf_capacity: int = 64,
                        znorm: bool = True, bound: str = "prefix",
                        backend: str = "ref") -> FlatIndex:
    """Build over series row-sharded over `axis` of `mesh`, as one
    program for the mesh in which each device builds its own block
    (`_build_body`, the body `build_index` runs): summarize (the Pallas
    kernel runs per device, never partitioned), sort by key, pad to whole
    leaves, leaf summaries and norms.  Block s of S holds rows s * n/S
    onwards, and its ids are offset by that.

    The result is leaf-sharded as `shard_index` places an index: every
    array split over `axis` by rows, each device holding the leaves of its
    own rows.  A device's leaves therefore cover its rows alone, not a
    range of the global key order; each block pads to whole leaves on its
    own, so padding (perm -1) can sit at the end of every block."""
    return _sharded_build_fn(mesh, axis, segments, bits, leaf_capacity,
                             znorm, bound, backend)(raw)


@functools.lru_cache(maxsize=None)
def _sharded_build_fn(mesh: Mesh, axis: str, segments: int, bits: int,
                      leaf_capacity: int, znorm: bool, bound: str,
                      backend: str):
    rows, entries = P(axis, None), P(axis)

    def local(block):
        first = jax.lax.axis_index(axis) * block.shape[0]
        return _build_body(block, segments=segments, bits=bits,
                           leaf_capacity=leaf_capacity, znorm=znorm,
                           bound=bound, backend=backend, first_row=first)

    out_specs = FlatIndex(series=rows, paa=rows, words=rows,
                          sq_norms=entries, perm=entries, valid=entries,
                          leaf_lo=rows, leaf_hi=rows, leaf_valid=entries)
    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=rows,
                                 out_specs=out_specs, check_vma=False))


def pad_leaves(idx: FlatIndex, multiple: int) -> FlatIndex:
    """Append fully-padded (invalid) leaves so n_leaves % multiple == 0.

    Padded leaves carry empty regions at +inf (lower bound = +inf, never a
    candidate) and perm == -1 entries, so search results are unchanged;
    this is what lets any index shard over any device count.
    """
    target = -(-idx.n_leaves // multiple) * multiple
    extra = target - idx.n_leaves
    if extra == 0:
        return idx
    M = idx.leaf_capacity
    L = idx.series.shape[1]
    w = idx.paa.shape[1]
    rows = extra * M
    big = jnp.float32(1e30)

    def cat(a, b):
        return jnp.concatenate([a, b], axis=0)

    return FlatIndex(
        series=cat(idx.series, jnp.zeros((rows, L), idx.series.dtype)),
        paa=cat(idx.paa, jnp.full((rows, w), jnp.inf, idx.paa.dtype)),
        words=cat(idx.words, jnp.zeros((rows, w), idx.words.dtype)),
        sq_norms=cat(idx.sq_norms, jnp.full((rows,), 1e30,
                                            idx.sq_norms.dtype)),
        perm=cat(idx.perm, jnp.full((rows,), -1, idx.perm.dtype)),
        valid=cat(idx.valid, jnp.zeros((rows,), idx.valid.dtype)),
        leaf_lo=cat(idx.leaf_lo, jnp.full((extra, w), big,
                                          idx.leaf_lo.dtype)),
        leaf_hi=cat(idx.leaf_hi, jnp.full((extra, w), big,
                                          idx.leaf_hi.dtype)),
        leaf_valid=cat(idx.leaf_valid, jnp.zeros((extra,),
                                                 idx.leaf_valid.dtype)),
    )


def build_index_host(raw: np.ndarray, executor, *,
                     segments: int = isax.SEGMENTS, bits: int = isax.SAX_BITS,
                     leaf_capacity: int = 64, n_threads: int = 8,
                     chunk_elems: int = 256):
    """Host control-plane build: the paper's BC -> TP pipeline verbatim.

    BC.TRAVERSE applies BUFFERCREATION over chunks of RawData under the given
    executor (Refresh or a baseline), PUTting (iSAX word, series id) pairs
    into 2^w-slot summarization buffers; TP.TRAVERSE inserts them into a
    forest of FatLeafTrees.  Used by the fidelity tests and the Figure 3/6/7/8
    benchmarks; the production path is build_index() above.

    Returns (forest dict bucket->FatLeafTree, buffers ArrayTraverse).
    """
    from .traverse import ArrayTraverse
    from .tree import FatLeafTree

    n = raw.shape[0]
    x = np.asarray(isax.znormalize(jnp.asarray(raw, jnp.float32)))
    paa_np = np.asarray(isax.paa(jnp.asarray(x), segments))
    words_np = np.asarray(isax.sax_word(jnp.asarray(paa_np), bits))
    buckets_np = np.asarray(isax.root_bucket(jnp.asarray(words_np), bits))

    # ---- BC: buffer creation over chunks of RawData ----------------------
    n_buckets_used = sorted(set(int(b) for b in buckets_np))
    slot_of = {b: i for i, b in enumerate(n_buckets_used)}
    buffers = ArrayTraverse(executor, n_slots=max(1, len(n_buckets_used)))

    chunk_ids = list(range(0, n, chunk_elems))

    def buffer_creation(chunk_start: int) -> None:
        hi = min(chunk_start + chunk_elems, n)
        for i in range(chunk_start, hi):
            buffers.put((words_np[i], i), slot_of[int(buckets_np[i])])

    bc = ArrayTraverse(executor)
    for c in chunk_ids:
        bc.put(c)
    bc.traverse(buffer_creation)

    # ---- TP: tree population, one subtree per summarization buffer -------
    forest = {b: FatLeafTree(segments, bits, leaf_capacity, n_threads)
              for b in n_buckets_used}

    # dense thread ids: announce slots must be unique per live thread
    # (`ident % n_threads` can collide, corrupting the announce protocol)
    import threading
    tid_map: dict = {}
    tid_lock = threading.Lock()

    def dense_tid() -> int:
        ident = threading.get_ident()
        with tid_lock:
            if ident not in tid_map:
                tid_map[ident] = len(tid_map) % n_threads
            return tid_map[ident]

    def tree_population(pair) -> None:
        word, idx = pair
        forest[int(buckets_np[idx])].insert(dense_tid(), word, int(idx),
                                            mode="standard")

    buffers.traverse(tree_population)
    return forest, buffers


def index_stats(idx: FlatIndex) -> dict:
    """Host-side summary used by benchmarks and EXPERIMENTS.md."""
    leaf_fill = np.asarray(jnp.sum(idx.valid.reshape(idx.n_leaves, -1), axis=1))
    return {
        "n_series": int(np.asarray(jnp.sum(idx.valid))),
        "n_leaves": int(idx.n_leaves),
        "leaf_capacity": idx.leaf_capacity,
        "mean_fill": float(leaf_fill.mean()),
        "min_fill": int(leaf_fill.min()),
        "max_fill": int(leaf_fill.max()),
    }
