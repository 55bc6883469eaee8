"""Exact k-NN query answering over the flat FreSh index (paper Section III/V).

Layering (PR 3): the module separates the PURE search computation from
knob resolution and dispatch so the facade and the serving layer share
one code path —

  search_plan_impl   the pure plan: fully-resolved knobs, (Q, k) outputs
                     plus the loop's counters (`COUNTERS`); traceable,
                     no jit
  search_plan        jax.jit(search_plan_impl) — what FreshIndex.search
                     dispatches through and what serve.PlanCache
                     AOT-compiles per (bucket, k) with .lower().compile()
  snapshot_search    one fused program over a (core, delta) epoch
                     snapshot: plan + exact delta scan + top-k merge
  run_search         knob resolution (explicit arg > IndexConfig >
                     default) + the historical k == 1 squeeze; the
                     facade folds a pending delta in via merge_delta_topk
  build_sharded_plan the pure sharded plan factory ((Q, k) outputs plus
                     the same `COUNTERS`, counted over the shards) — what
                     the sharded facade and serving path run
  build_sharded_search
                     jit + squeeze over build_sharded_plan, behind the
                     deprecated make_sharded_search
  search / make_sharded_search
                     DEPRECATED free-function shims (DeprecationWarning
                     pointing at the repro.api migration table)

The four traverse-object stages map to:

  pruning    — ONE vectorized lower-bound computation over all leaf
               summaries (Pallas kernel on TPU), instead of a tree walk;
  RS / the priority queues
             — two-stage partial selection over the leaf lower bounds:
               jax.lax.top_k picks (and orders — top_k returns sorted
               values, so the within-budget argsort is fused into the
               selection) only the R leaves the refinement loop can ever
               consume, where R is calibrated from the round budget
               (R = n_rounds_cap * K, further capped by pq_budget).  The
               selected ascending order IS the DeleteMin order of the
               paper's queues; PQ setup is O(NL + R log R) per query
               instead of the full argsort's O(NL log NL);
  refinement — a while_loop over ROUNDS: each round takes the next K best
               leaves per query, computes real distances in matmul form
               (dist^2 = ||q||^2 + ||x||^2 - 2 q.x  -> MXU), and folds the
               min into BSF.  The loop exits as soon as the next unrefined
               lower bound >= BSF — exactly the PQ termination condition, so
               the answer is EXACT.  backend='pallas' runs the whole round
               body through the fused kernels.refine_topk (gather +
               distances + prune + top-k fold in VMEM — no (Q, K*M, L)
               intermediate ever reaches HBM); backend='ref' is the
               materializing pure-jnp path.  The two are bit-comparable in
               interpret mode: identical entry buffers and final
               distances (which are recomputed in direct form from the
               winners), with intra-round f32 sums equal to the last ulp.
               Rounds run in lockstep over the batch, and a query that has
               finished stays finished (its next lower bound only grows,
               its k-th BSF only shrinks), so the loop runs in PHASES of
               halving width (`phase_widths`): a phase refines only its
               rows, and between phases the live rows are gathered to the
               front of a batch of half the width.  A finished query's
               refine steps then leave the kernel's grid, and no query's
               rounds, pruning or answer change.

Expeditive vs standard (Section IV) on the mesh: in the sharded search each
device refines against its LOCAL BSF (no communication = expeditive mode)
and only every `sync_every` rounds performs the all-reduce-min that
publishes the global BSF (= standard mode).  sync_every trades
synchronization cost against wasted refinement work — the exact trade-off
Refresh manages between its two modes.
"""

from __future__ import annotations

import functools
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import isax
from .index import FlatIndex

BIG = jnp.float32(1e30)

#: what the plans' counter output holds, in order: refinement rounds;
#: (query, round) pairs in which the query was live; (query, leaf) pairs
#: whose distances the refine round computed; the rows the refine call
#: ran with, summed over rounds; the rounds in which a shard refined at
#: least one pair.  The sharded plan counts the same over S shards:
#: rounds of its collective loop, a query live where it is live on any
#: shard, and the last three summed over the shards (one device: the
#: last equals the rounds)
COUNTERS = ("rounds", "live_query_rounds", "refined_pairs", "kernel_rows",
            "shard_live_rounds")

#: the narrowest batch the plan refines in (`phase_widths`)
PHASE_FLOOR = 8


_BACKENDS = ("ref", "pallas")


def _resolve_knob(value, config, name: str, fallback):
    """Explicit argument wins; otherwise the index's IndexConfig field;
    the hard fallback only when neither is given (e.g. backend -> 'ref',
    the old hard default)."""
    if value is not None:
        return value
    if config is not None and getattr(config, name, None) is not None:
        return getattr(config, name)
    return fallback


def _resolve_backend(backend, config) -> str:
    """Like _resolve_knob('backend') but validated: IndexConfig checks its
    own field, so a per-call override is the one path a typo ('Pallas',
    'mosaic') could otherwise silently fall through to the ref branch."""
    bk = _resolve_knob(backend, config, "backend", "ref")
    if bk not in _BACKENDS:
        raise ValueError(f"backend must be one of {_BACKENDS}, got {bk!r}")
    return bk


def _rounds_cap(n_leaves: int, K: int, max_rounds: Optional[int],
                pq_budget: Optional[int]) -> int:
    """Static bound on refinement rounds: enough to cover every leaf,
    tightened by max_rounds and/or the pq_budget leaf allowance."""
    cap = -(-n_leaves // K)
    if max_rounds is not None:
        cap = min(cap, max_rounds)
    if pq_budget is not None:
        cap = min(cap, max(1, -(-pq_budget // K)))
    return cap


def _stop_knobs(stop_eps: float, stop_leaves: Optional[int],
                pq_budget: Optional[int]) -> Tuple[float, Optional[int]]:
    """Validate the early-termination knobs (repro.quality stop rules)
    and fold the `stop_leaves` visited-leaf cap into the PQ leaf budget.

    Returns `(inv_eps_sq, leaf_budget)`: the squared-space bound scale
    1/(1+eps)^2 the while_loop cond multiplies the k-th BSF by (1.0 in
    exact mode — the guard at every call site keeps the traced program
    literally unchanged when both knobs are defaults), and the combined
    leaf allowance (min of pq_budget and stop_leaves, None = uncapped).
    """
    if stop_eps < 0.0:
        raise ValueError(f"stop_eps must be >= 0, got {stop_eps}")
    if stop_leaves is not None and stop_leaves < 1:
        raise ValueError(f"stop_leaves must be >= 1 or None, "
                         f"got {stop_leaves}")
    inv = 1.0 if stop_eps == 0.0 else 1.0 / float(1.0 + stop_eps) ** 2
    if stop_leaves is None:
        budget = pq_budget
    elif pq_budget is None:
        budget = stop_leaves
    else:
        budget = min(pq_budget, stop_leaves)
    return inv, budget


def _pq_order(lb: jnp.ndarray, K: int, n_rounds_cap: int,
              leaf_budget: Optional[int] = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Two-stage partial-selection priority queue.

    The refinement loop reads at most n_rounds_cap * K PQ entries, so only
    the R = min(n_rounds_cap * K, NL) best leaves need selecting and
    ordering: jax.lax.top_k over -lb picks them AND returns them sorted
    (ascending in lb, ties to the lower leaf index — the same permutation
    prefix a full stable argsort would produce), dropping PQ setup from
    O(NL log NL) to O(NL + R log R) per query.  `leaf_budget` (pq_budget)
    is an exact cap on admitted leaves, not rounded up to whole rounds.
    Entries past R are padded with lb=BIG so every dynamic_slice of width
    K stays in range and padded slots never pass the pruning test.
    """
    NL = lb.shape[1]
    R = min(n_rounds_cap * K, NL)
    if leaf_budget is not None:
        R = max(1, min(R, leaf_budget))
    neg, order = jax.lax.top_k(-lb, R)
    sorted_lb = -neg
    padw = n_rounds_cap * K - R
    if padw > 0:
        order = jnp.pad(order, ((0, 0), (0, padw)))
        sorted_lb = jnp.pad(sorted_lb, ((0, 0), (0, padw)),
                            constant_values=BIG)
    return order, sorted_lb


#: Queries are prepared (z-normalized, PAA, squared norm) and their
#: winners' distances recomputed in fixed tiles of this many rows.  XLA
#: on TPU picks a reduction's strategy from the whole array's shape: one
#: query normalized alone, in a batch of 16 and in a batch of 128 came
#: out with different last bits.  In fixed tiles a query's answer does
#: not depend on the batch it rode in, so an engine bucket returns the
#: facade's bits.
QUERY_TILE = 128


def per_query_tiles(fn, *rows):
    """`fn(*tiles)` over the leading (query) axis of `rows`, in tiles of
    QUERY_TILE rows (zero-padded; the pad rows' outputs are dropped).
    Every tile runs the same program between optimization barriers, so
    neither the batch size nor the surrounding program can change how
    one row is computed.  At least one pad row is always added: with
    none (Q a multiple of QUERY_TILE) the tiles were the caller's array
    itself, and on TPU that program came out one ulp away from the
    padded one."""
    Q = rows[0].shape[0]
    T = Q // QUERY_TILE + 1
    pad = T * QUERY_TILE - Q
    tiles = tuple(
        jnp.pad(r, ((0, pad),) + ((0, 0),) * (r.ndim - 1))
        .reshape((T, QUERY_TILE) + r.shape[1:]) for r in rows)

    def one(tile):
        return jax.lax.optimization_barrier(
            fn(*jax.lax.optimization_barrier(tile)))

    out = jax.lax.map(one, tiles)
    return jax.tree.map(
        lambda o: o.reshape((T * QUERY_TILE,) + o.shape[2:])[:Q], out)


def prepare_query_rows(queries: jnp.ndarray, znorm: bool = True,
                       segments: Optional[int] = None,
                       index: Optional[FlatIndex] = None):
    """`prepare_queries` plus each query's squared norm: (q, q_paa,
    q_sq), computed row by row in QUERY_TILE tiles (`per_query_tiles`)."""
    if index is not None:
        segments = index.paa.shape[1]
    if segments is None:
        segments = isax.SEGMENTS
    L = queries.shape[-1]
    if L % segments != 0:
        raise ValueError(
            f"query length {L} is not divisible by the index segment count "
            f"{segments}; queries must have the same length as the indexed "
            f"series (pad the feature dim up to a segment multiple)")

    def prep(x):
        q = isax.znormalize(x) if znorm else x
        q = q.astype(jnp.float32)
        return q, isax.paa(q, segments), jnp.sum(q * q, axis=-1)

    return per_query_tiles(prep, queries)


def prepare_queries(queries: jnp.ndarray, znorm: bool = True,
                    segments: Optional[int] = None,
                    index: Optional[FlatIndex] = None):
    """Normalize queries and compute their PAA at the index's segment count.

    The segment count MUST match the index the queries will be matched
    against — a silent mismatch makes every lower bound meaningless.  Pass
    either `index` (preferred: segments are derived from it, which is what
    `FreshIndex.search` does) or an explicit `segments`; when neither is
    given the library default `isax.SEGMENTS` is used.  Raises ValueError
    when the series length is not divisible by the segment count (the old
    behaviour silently fell back to `segments = L`, producing PAA widths
    that disagree with the index).
    """
    q, q_paa, _ = prepare_query_rows(queries, znorm, segments, index)
    return q, q_paa


def direct_sq(q: jnp.ndarray, series: jnp.ndarray,
              entries: jnp.ndarray) -> jnp.ndarray:
    """Squared distances of (Q, L) queries to their (Q, k) `entries` of
    `series`, in direct form (sum of squared differences; the matmul form
    loses ~1e-3 absolute to f32 cancellation), in QUERY_TILE tiles."""
    return per_query_tiles(
        lambda qt, et: jnp.sum(jnp.square(qt[:, None, :] - series[et]),
                               axis=-1),
        q, entries)


def leaf_lower_bounds(idx: FlatIndex, q_paa: jnp.ndarray,
                      series_len: int, backend: str = "ref") -> jnp.ndarray:
    """(Q, n_leaves) squared lower bounds — the pruning stage.

    backend 'pallas' routes through the fused Pallas MINDIST kernel
    (Mosaic on TPU, interpret mode elsewhere); 'ref' is the pure-jnp path.
    """
    if backend == "pallas":
        from repro.kernels import ops
        return ops.lb_distance(q_paa, idx.leaf_lo, idx.leaf_hi,
                               series_len=series_len)
    return isax.mindist_region_sq(q_paa[:, None, :],
                                  idx.leaf_lo[None],
                                  idx.leaf_hi[None],
                                  series_len)


def phase_widths(Q: int) -> Tuple[int, ...]:
    """The batch widths the plan's refinement phases run at, in order:
    Q, then each half (rounded down) that still holds PHASE_FLOOR rows.
    A batch of fewer than 2 * PHASE_FLOOR rows runs in one phase."""
    widths = [Q]
    while widths[-1] // 2 >= PHASE_FLOOR:
        widths.append(widths[-1] // 2)
    return tuple(widths)


def _refine_round(q, q_sq, series, sq_norms, ids, alive, bsf_d, bsf_e,
                  *, M: int, k: int, backend: str,
                  dma_depth: int = 1):
    """One refinement round: distances of the addressed leaves' members,
    pruned by `alive`, folded into the (Q, k) BSF buffer.

    The single dispatch point both the local and sharded loops share.
    'pallas' is the fused allocation-free kernel; 'ref' is the
    materializing oracle in kernels.ref (gather (Q, K*M, L), matmul-form
    distances — the MXU-feeding layout — mask, lax.top_k merge).  Entries
    never repeat across rounds (leaves are disjoint; padded duplicate PQ
    slots carry lb=BIG and fail `alive`), so the buffer stays
    duplicate-free.

    `dma_depth` is a pallas-only kernel-structure knob (kernels.refine;
    normally resolved through the autotune table) — the ref backend
    ignores it, and callers normalize it to the default there so it
    never splits its compile cache.
    """
    from repro.kernels import ops, ref
    if backend == "pallas":
        return ops.refine_topk(q, q_sq, series, sq_norms, ids, alive,
                               bsf_d, bsf_e, leaf_capacity=M, k=k,
                               dma_depth=dma_depth)
    return ref.refine_topk_ref(q, q_sq, series, sq_norms, ids, alive,
                               bsf_d, bsf_e, leaf_capacity=M, k=k)


def search_plan_impl(idx: FlatIndex, queries: jnp.ndarray, *,
                     k: int = 1, round_leaves: int = 8, znorm: bool = True,
                     max_rounds: Optional[int] = None, backend: str = "ref",
                     pq_budget: Optional[int] = None,
                     stop_eps: float = 0.0,
                     stop_leaves: Optional[int] = None,
                     dma_depth: int = 1
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The PURE search plan: exact k-NN with every knob fully resolved.

    This is the one computation both `FreshIndex.search` and the serving
    layer (`repro.serve`) execute — the facade traces it through the
    jitted `search_plan`, the serving PlanCache AOT-compiles the very same
    jaxpr per (batch-bucket, k) with `.lower().compile()`, so the two are
    bit-identical on the same snapshot.  No knob resolution, no squeezing,
    no dispatch happens here; callers pass concrete values.

    Returns (dist, original_id, counts): dist/id are (Q, k) ascending by
    distance (no k == 1 squeeze — see `run_search`); counts is the (5,)
    int32 array of the loop's own counters, in `COUNTERS` order:
    the refinement rounds the loop executed (the paper's DeleteMin
    count; the serving layer surfaces it as rounds-per-query), the
    (query, round) pairs in which the query was still live (its next
    unrefined lower bound beat its k-th best-so-far), the (query,
    leaf) pairs the refine round computed distances for (`alive`; dead
    slots skip both their copy and their arithmetic), and the rows the
    refine call ran with, summed over rounds (its grid is those rows
    times K), and the rounds in which the one shard refined anything
    (here every round: a round runs only while some query is live).
    The counts stay on the device; reading them is the caller's
    choice.

    Rounds run in lockstep over the batch, in phases of the widths
    `phase_widths(Q)` gives.  A phase of B rows runs while more of them
    are live than the next phase has rows (the last phase: while any is
    live), all under the `n_rounds_cap` cap.  Between phases every row
    is written back to the (Q, k) buffers, and the live rows are
    gathered, with their queries and priority queues, to the front of
    the next, narrower batch; dead rows fill the rest.  A query that has
    died never comes back (its next lower bound only grows, its k-th
    best-so-far only shrinks), and a dead row's round changes nothing,
    so a row leaves the batch with its final buffer.  The refine kernel
    computes one program row per query, so a query's bits do not depend
    on the rows beside it: each query's rounds, pruning and answer are
    those of one batch of Q rows.  At Q < 2 * PHASE_FLOOR there is one
    phase, and the loop is that one batch.

    The BSF scalar of the paper generalizes to a per-query top-k buffer:
    each refinement round's real distances are folded in with
    jax.lax.top_k and the PQ termination condition compares the next
    unrefined lower bound against the k-th best-so-far (the buffer's
    worst member).  `pq_budget` caps the number of leaves admitted to the
    priority queue: like `max_rounds`, a budget too small for the
    termination condition to trigger makes distances upper bounds instead
    of exact.

    `stop_eps` / `stop_leaves` are the repro.quality APPROXIMATE stop
    rules (static knobs — one compiled program per setting, zero traces
    per query): stop_eps relaxes the PQ termination to "stop once no
    unrefined lower bound can beat bsf/(1+eps)" (compared in squared
    space as lb >= bsf^2/(1+eps)^2), and stop_leaves hard-caps the
    visited leaves by tightening the PQ leaf budget.  At the defaults
    (0.0, None) the traced program is LITERALLY the exact one — the
    guards below emit the unscaled expressions — so exact mode stays
    bit-identical to the seed oracle.

    `dma_depth` picks the pallas refine-kernel structure
    (kernels.refine: pipelined kernel at 1, explicit DMA ring above) —
    an autotune-resolved knob that changes HOW the round executes, never
    WHAT it returns.  The ref backend ignores it (callers normalize it
    to 1 there).
    """
    if backend not in _BACKENDS:
        raise ValueError(f"backend must be one of {_BACKENDS}, "
                         f"got {backend!r}")
    inv_eps, leaf_budget = _stop_knobs(stop_eps, stop_leaves, pq_budget)
    L = idx.series.shape[1]
    Q = queries.shape[0]
    K = round_leaves
    M = idx.leaf_capacity
    n_leaves = idx.n_leaves

    q, q_paa, q_sq = prepare_query_rows(queries, znorm, index=idx)

    lb = leaf_lower_bounds(idx, q_paa, L, backend)     # (Q, n_leaves)

    n_rounds_cap = _rounds_cap(n_leaves, K, max_rounds, leaf_budget)
    order, sorted_lb = _pq_order(lb, K, n_rounds_cap, leaf_budget)

    cap = n_rounds_cap * K
    # the refine kernel reads the leaf norms as (NL, 1, M) rows, laid out
    # once here: XLA does not hoist a relayout that pads lanes out of a
    # loop, and run once a round it rivals the round's own kernel
    norms = (idx.sq_norms.reshape(-1, 1, M) if backend == "pallas"
             else idx.sq_norms)

    def live_rows(cursor, sorted_lb, bsf_d):
        # PQ termination: stop when the best unrefined lb >= the k-th BSF
        # (scaled by 1/(1+eps)^2 in approx mode: no remaining candidate
        # can improve the k-th answer by more than the (1+eps) factor)
        nxt = jax.lax.dynamic_slice_in_dim(sorted_lb, cursor, K, axis=1)
        bound = bsf_d[:, -1] * inv_eps if stop_eps else bsf_d[:, -1]
        return nxt[:, 0] < bound

    def phase(q, q_sq, order, sorted_lb, state, keep: int):
        """Rounds over the batch's rows while more than `keep` of them
        are live (keep == 0: while any is)."""
        def cond(state):
            cursor, bsf_d = state[:2]
            live_q = live_rows(cursor, sorted_lb, bsf_d)
            live = jnp.sum(live_q) > keep if keep else jnp.any(live_q)
            return jnp.logical_and(cursor < cap, live)

        def body(state):
            cursor, bsf_d, bsf_e, live, refined = state
            ids = jax.lax.dynamic_slice_in_dim(order, cursor, K, axis=1)
            lbs = jax.lax.dynamic_slice_in_dim(sorted_lb, cursor, K, axis=1)
            # prune: leaves whose lb >= the current k-th BSF contribute
            # nothing (approx mode shares the eps-scaled bound with cond)
            bound = (bsf_d[:, -1:] * inv_eps if stop_eps else bsf_d[:, -1:])
            alive = (lbs < bound)                        # (B, K)
            bsf_d, bsf_e = _refine_round(q, q_sq, idx.series, norms,
                                         ids, alive, bsf_d, bsf_e,
                                         M=M, k=k, backend=backend,
                                         dma_depth=dma_depth)
            # the PQ is sorted, so slot 0 says whether the query is live
            live = live + jnp.sum(alive[:, 0], dtype=jnp.int32)
            refined = refined + jnp.sum(alive, dtype=jnp.int32)
            return cursor + K, bsf_d, bsf_e, live, refined

        return jax.lax.while_loop(cond, body, state)

    widths = phase_widths(Q)
    batch = (q, q_sq, order, sorted_lb)
    state = (jnp.int32(0), jnp.full((Q, k), BIG),
             jnp.zeros((Q, k), jnp.int32), jnp.int32(0), jnp.int32(0))
    kernel_rows = jnp.int32(0)
    rows = None                 # the batch's rows of the (Q, k) buffers
    for B, nxt_w in zip(widths, widths[1:] + (0,)):
        start = state[0]
        state = phase(*batch, state, keep=nxt_w)
        cursor, bsf_d, bsf_e, live, refined = state
        kernel_rows = kernel_rows + B * ((cursor - start) // K)
        if rows is None:
            out_d, out_e = bsf_d, bsf_e
        else:
            out_d = out_d.at[rows].set(bsf_d, unique_indices=True)
            out_e = out_e.at[rows].set(bsf_e, unique_indices=True)
        if nxt_w:
            # live rows first (stable), dead ones after to fill the width
            dead = jnp.where(live_rows(cursor, batch[3], bsf_d), 0, 1)
            front = jnp.argsort(dead)[:nxt_w]
            rows = front if rows is None else rows[front]
            batch = tuple(a[front] for a in batch)
            state = (cursor, bsf_d[front], bsf_e[front], live, refined)
    bsf_d, bsf_e = out_d, out_e

    # the top-k set is exact; the matmul-form distance loses ~1e-3 absolute
    # to f32 cancellation (||q||^2+||x||^2-2qx with ||.||^2 ~ L).  Recompute
    # the winners' distances in direct form — k gathers per query — and
    # re-sort the buffer by the exact values.
    found = bsf_d < BIG                                  # (Q, k)
    ids = jnp.where(found, idx.perm[bsf_e], -1)
    d = jnp.where(found, direct_sq(q, idx.series, bsf_e), bsf_d)
    resort = jnp.argsort(d, axis=1)
    d = jnp.sqrt(jnp.take_along_axis(d, resort, axis=1))
    ids = jnp.take_along_axis(ids, resort, axis=1)
    rounds = cursor // K
    return d, ids, jnp.stack([rounds, live, refined, kernel_rows, rounds])


search_plan = functools.partial(
    jax.jit, static_argnames=("k", "round_leaves", "znorm", "max_rounds",
                              "backend", "pq_budget", "stop_eps",
                              "stop_leaves", "dma_depth"))(search_plan_impl)
search_plan.__doc__ = search_plan_impl.__doc__


def _bruteforce_topk(raw: jnp.ndarray, queries: jnp.ndarray,
                     *, k: int, znorm: bool,
                     alive: Optional[jnp.ndarray] = None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(Q, k) exact scan over all series (matmul-form selection, direct-form
    reported distances) — the traceable body of `search_bruteforce`.

    `alive` (an (n,) bool mask, None = all rows) makes the scan
    tombstone-aware: masking happens on the DISTANCES, after
    normalization, because mangling a dead row's values would hit the
    zero-variance znorm path and produce small (wrong) distances.  A
    dead row can still be *selected* when k exceeds the alive count;
    such slots report the BIG sentinel distance and id -1, exactly like
    the index search's not-found slots."""
    x = isax.znormalize(raw).astype(jnp.float32) if znorm \
        else raw.astype(jnp.float32)
    q, _, q_sq = prepare_query_rows(queries, znorm, segments=1)
    d2 = (q_sq[:, None] + jnp.sum(x * x, -1)[None, :]
          - 2.0 * jnp.dot(q, x.T, precision=jax.lax.Precision.HIGHEST))
    d2 = jnp.maximum(d2, 0.0)
    if alive is not None:
        d2 = jnp.where(alive[None, :], d2, BIG)
    _, i = jax.lax.top_k(-d2, k)                        # (Q, k)
    d_exact = direct_sq(q, x, i)
    if alive is not None:
        d_exact = jnp.where(alive[i], d_exact, BIG)
    resort = jnp.argsort(d_exact, axis=1)               # see search(): exact
    d = jnp.sqrt(jnp.take_along_axis(d_exact, resort, axis=1))
    i = jnp.take_along_axis(i.astype(jnp.int32), resort, axis=1)
    if alive is not None:
        i = jnp.where(alive[i], i, -1)
    return d, i


def _merge_topk(d_a, i_a, d_b, i_b, k: int):
    """Fold two (Q, *) candidate sets into the (Q, k) best, ties to set a."""
    alld = jnp.concatenate([d_a, d_b], axis=1)
    alli = jnp.concatenate([i_a, i_b], axis=1)
    neg, pos = jax.lax.top_k(-alld, k)
    return -neg, jnp.take_along_axis(alli, pos, axis=1)


def _shift_delta_ids(di: jnp.ndarray, n_base: int,
                     delta_alive: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Delta scan position -> series id.  `n_base` is the DELTA ID
    OFFSET: delta position p holds series id `n_base + p` (historically
    equal to the core row count; after a tombstone-dropping compaction
    ids are sparse and the offset keeps counting from the high-water
    mark).  With a tombstone mask, not-found slots carry position -1 and
    must stay -1 rather than alias id `n_base - 1`."""
    if delta_alive is None:
        return di + n_base
    return jnp.where(di >= 0, di + n_base, -1)


def snapshot_search_impl(idx: FlatIndex, delta: jnp.ndarray,
                         queries: jnp.ndarray,
                         delta_alive: Optional[jnp.ndarray] = None,
                         *, k: int, n_base: int,
                         round_leaves: int = 8, znorm: bool = True,
                         max_rounds: Optional[int] = None,
                         backend: str = "ref",
                         pq_budget: Optional[int] = None,
                         stop_eps: float = 0.0,
                         stop_leaves: Optional[int] = None,
                         dma_depth: int = 1
                         ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Search plan over a (core index, delta buffer) epoch snapshot.

    The Jiffy-style snapshot the serving layer publishes on add(): the
    pruned core index answers via `search_plan_impl`, the unsorted (m, L)
    delta is scanned EXACTLY, and the two candidate sets merge into one
    (Q, k) result whose delta ids continue at the `n_base` id offset.
    One fused program, AOT-compiled once per published epoch by
    serve.PlanCache.  (The facade instead keeps its cached core program
    and re-jits only `merge_delta_topk` — cheaper for add-heavy one-shot
    use, where every add would otherwise recompile the whole plan.)

    Tombstones: dead CORE rows arrive pre-masked (the caller passes a
    `maintenance.mask_core` view whose dead norms are the BIG sentinel);
    dead DELTA rows are masked here via `delta_alive` (an (m,) bool
    mask, None = all alive).

    `stop_eps` / `stop_leaves` apply to the CORE plan only (see
    `search_plan_impl`): the delta scan stays exact — it is one matmul
    over the (small) pending buffer, so skipping any of it would trade
    recall for nothing.  The counter output is the core plan's.
    """
    d, i, counts = search_plan_impl(
        idx, queries, k=k, round_leaves=round_leaves, znorm=znorm,
        max_rounds=max_rounds, backend=backend, pq_budget=pq_budget,
        stop_eps=stop_eps, stop_leaves=stop_leaves,
        dma_depth=dma_depth)
    kd = min(k, delta.shape[0])
    dd, di = _bruteforce_topk(delta, queries, k=kd, znorm=znorm,
                              alive=delta_alive)
    md, mi = _merge_topk(d, i, dd,
                         _shift_delta_ids(di, n_base, delta_alive), k)
    return md, mi, counts


snapshot_search = functools.partial(
    jax.jit, static_argnames=("k", "n_base", "round_leaves", "znorm",
                              "max_rounds", "backend", "pq_budget",
                              "stop_eps", "stop_leaves",
                              "dma_depth"))(snapshot_search_impl)
snapshot_search.__doc__ = snapshot_search_impl.__doc__


@functools.partial(jax.jit, static_argnames=("k", "n_base", "znorm"))
def merge_delta_topk(delta: jnp.ndarray, queries: jnp.ndarray,
                     d: jnp.ndarray, i: jnp.ndarray,
                     delta_alive: Optional[jnp.ndarray] = None, *, k: int,
                     n_base: int, znorm: bool = True
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fold an exact delta scan into already-computed (Q, k) main-index
    results — the sharded facade path, where the core answer comes from a
    separate shard_map program and only the merge runs here.  `n_base`
    is the delta id offset and `delta_alive` the optional tombstone
    mask (see `snapshot_search_impl`)."""
    kd = min(k, delta.shape[0])
    dd, di = _bruteforce_topk(delta, queries, k=kd, znorm=znorm,
                              alive=delta_alive)
    return _merge_topk(d, i, dd,
                       _shift_delta_ids(di, n_base, delta_alive), k)


def squeeze_k(d: jnp.ndarray, i: jnp.ndarray, k: int):
    """The historical 1-NN interface: (Q, 1) -> (Q,) when k == 1."""
    if k == 1:
        return d[:, 0], i[:, 0]
    return d, i


def run_search(idx: FlatIndex, queries: jnp.ndarray, *,
               k: int = 1, round_leaves: Optional[int] = None,
               znorm: bool = True, max_rounds: Optional[int] = None,
               backend: Optional[str] = None,
               pq_budget: Optional[int] = None,
               stop_eps: float = 0.0, stop_leaves: Optional[int] = None,
               dma_depth: Optional[int] = None,
               tune=None, config=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Knob resolution + dispatch over the jitted `search_plan` — the
    facade's entry point (no deprecation warning; `search` is the warning
    shim around this).  backend / round_leaves / pq_budget / dma_depth
    default to None and resolve explicit arg > `config` field (an
    IndexConfig — what FreshIndex.search passes) > `tune` (a
    kernels.autotune.TuneConfig — the FRESH tuned entry for this device,
    what FreshIndex.search passes when a table is installed) > the static
    defaults 'ref' / 8 / uncapped / 1; stop_eps / stop_leaves are the
    repro.quality approximate stop rules (defaults = exact).
    Returns (Q,) arrays for k == 1, (Q, k) ascending otherwise."""
    t = tune
    K = _resolve_knob(round_leaves, config, "round_leaves",
                      t.round_leaves if t else 8)
    bk = _resolve_backend(backend, config)
    pq_budget = _resolve_knob(pq_budget, config, "pq_budget",
                              t.pq_budget if t else None)
    dd = _resolve_knob(dma_depth, config, "dma_depth",
                       t.dma_depth if t else 1)
    if bk != "pallas":
        dd = 1               # ref ignores it; don't split its jit cache
    d, i, _ = search_plan(idx, queries, k=k, round_leaves=K, znorm=znorm,
                          max_rounds=max_rounds, backend=bk,
                          pq_budget=pq_budget, stop_eps=stop_eps,
                          stop_leaves=stop_leaves, dma_depth=dd)
    return squeeze_k(d, i, k)


def _warn_deprecated_free_function(old: str, new: str) -> None:
    warnings.warn(
        f"calling repro.core.search.{old} directly is deprecated; use "
        f"{new} instead (see the migration table in repro.api and the "
        f"README)", DeprecationWarning, stacklevel=3)


def search(idx: FlatIndex, queries: jnp.ndarray, *,
           k: int = 1, round_leaves: Optional[int] = None,
           znorm: bool = True, max_rounds: Optional[int] = None,
           backend: Optional[str] = None, pq_budget: Optional[int] = None,
           config=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """DEPRECATED free-function spelling of exact k-NN.

    Kept as a compatibility shim over `run_search` (knob resolution) +
    `search_plan` (the pure plan).  New code: `FreshIndex.search(q, k=...)`
    for one-shot batches, `FreshIndex.engine()` for serving loops.
    """
    _warn_deprecated_free_function(
        "search", "FreshIndex.search(q, k=...) or FreshIndex.engine()")
    return run_search(idx, queries, k=k, round_leaves=round_leaves,
                      znorm=znorm, max_rounds=max_rounds, backend=backend,
                      pq_budget=pq_budget, config=config)


@functools.partial(jax.jit, static_argnames=("k", "znorm"))
def search_bruteforce(raw: jnp.ndarray, queries: jnp.ndarray,
                      *, k: int = 1, znorm: bool = True,
                      alive: Optional[jnp.ndarray] = None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k oracle: exact scan over all series (matmul form).

    Candidate selection uses the same matmul-form distances as the index
    search; reported distances are recomputed in direct form.  Returns
    shapes (Q,) for k == 1, (Q, k) ascending otherwise.  k and znorm are
    keyword-only: the old signature had znorm third, and a positional k
    would silently reinterpret those call sites.  NOT deprecated: this is
    the testing oracle the migration table keeps.

    `alive` ((n,) bool, None = all rows) makes it the TOMBSTONE-AWARE
    oracle: dead rows never win, over-large k reports (BIG, -1) slots —
    what the lifecycle tests compare every search layer against.
    """
    d, i = _bruteforce_topk(raw, queries, k=k, znorm=znorm, alive=alive)
    return squeeze_k(d, i, k)


# ===========================================================================
# Sharded search: leaves block-sharded over the 'data' mesh axis.
# ===========================================================================
def shard_index(idx: FlatIndex, mesh: Mesh, axis: str = "data") -> FlatIndex:
    """Place the index with leaves (and their entries) sharded over `axis`."""
    leaf_spec = NamedSharding(mesh, P(axis))
    entry_spec = NamedSharding(mesh, P(axis))
    mat_spec = NamedSharding(mesh, P(axis, None))
    return FlatIndex(
        series=jax.device_put(idx.series, mat_spec),
        paa=jax.device_put(idx.paa, mat_spec),
        words=jax.device_put(idx.words, mat_spec),
        sq_norms=jax.device_put(idx.sq_norms, entry_spec),
        perm=jax.device_put(idx.perm, entry_spec),
        valid=jax.device_put(idx.valid, entry_spec),
        leaf_lo=jax.device_put(idx.leaf_lo, mat_spec),
        leaf_hi=jax.device_put(idx.leaf_hi, mat_spec),
        leaf_valid=jax.device_put(idx.leaf_valid, leaf_spec),
    )


def build_sharded_plan(mesh: Mesh, *, axis: str = "data", k: int = 1,
                       round_leaves: Optional[int] = None,
                       sync_every: int = 1,
                       max_rounds: Optional[int] = None, znorm: bool = True,
                       backend: Optional[str] = None,
                       pq_budget: Optional[int] = None,
                       stop_eps: float = 0.0,
                       stop_leaves: Optional[int] = None,
                       dma_depth: Optional[int] = None,
                       tune=None, config=None):
    """The PURE sharded search plan factory: `(idx, queries) -> (dist,
    ids, counts)` with (Q, k) outputs and no squeeze — the sharded
    analogue of `search_plan_impl`.

    Each device: local lower bounds + local partial-selection PQ + local
    refinement rounds against a LOCAL top-k BSF buffer (expeditive); every
    `sync_every` rounds the global k-th bound is published with an
    all-reduce-min (standard mode).  Soundness of the published bound: each
    device's local k-th BSF is an upper bound on the global k-th distance
    (its k candidates are all <= it and all belong to the union), so the
    pmin over devices is too.  The final (dist, id) top-k is resolved by
    all-gathering the n_dev local buffers and re-top-k'ing the union.
    The loop is collective: after each round an all-reduce-max tells
    every device which queries are still live on some device, and the
    loop runs while any is, so every device executes the same rounds.

    `counts` is the (5,) int32 `COUNTERS` array, replicated: the rounds
    of the collective loop; the (query, round) pairs in which the query
    was live on any device; and, summed over the devices, the (query,
    leaf) pairs refined, the rows the refine calls ran with (every round
    refines all Q rows on every device), and the rounds in which the
    device refined at least one pair — a device whose queries are all
    pruned locally still runs the round, at its lock-step cost.

    The returned function is traceable but NOT jitted: `FreshIndex.search`
    and the serving layer (`serve.PlanCache`, AOT per (batch-bucket, k,
    mesh layout)) jit the very same function, so the two paths execute
    identical programs.

    backend / round_leaves / pq_budget / dma_depth resolve from
    `config` (IndexConfig) when unset, then from `tune` (a fresh autotune
    TuneConfig, the same fallback layer `run_search` uses), then from the
    hard defaults — like the local search().  backend='pallas' routes
    each device's refine closure through the fused kernels.refine_topk,
    which is where dma_depth lands; the ref backend ignores it, so it
    is normalized to 1 there to keep one jit entry.

    `stop_eps` / `stop_leaves` are the repro.quality approximate stop
    rules, lowered into the collective while_loop cond exactly like the
    local plan (see `search_plan_impl`; defaults = the bit-identical
    exact program).  `stop_leaves` caps visited leaves PER SHARD — the
    natural sharded reading of the budget, since every device refines
    its own PQ — so a mesh of D devices visits at most D * stop_leaves
    leaves in total.
    """
    t = tune
    K = _resolve_knob(round_leaves, config, "round_leaves",
                      t.round_leaves if t else 8)
    bk = _resolve_backend(backend, config)
    pq_budget = _resolve_knob(pq_budget, config, "pq_budget",
                              t.pq_budget if t else None)
    dd = _resolve_knob(dma_depth, config, "dma_depth",
                       t.dma_depth if t else 1)
    if bk != "pallas":
        dd = 1               # ref ignores it; don't split its jit cache
    inv_eps, leaf_budget = _stop_knobs(stop_eps, stop_leaves, pq_budget)

    def _local_search(series, sq_norms, perm, leaf_lo, leaf_hi, q, q_paa, q_sq):
        L = series.shape[1]
        Q = q.shape[0]
        n_leaves_local = leaf_lo.shape[0]
        M = series.shape[0] // n_leaves_local

        if bk == "pallas":
            from repro.kernels import ops
            lb = ops.lb_distance(q_paa, leaf_lo, leaf_hi, series_len=L)
        else:
            lb = isax.mindist_region_sq(q_paa[:, None, :], leaf_lo[None],
                                        leaf_hi[None], L)

        cap = _rounds_cap(n_leaves_local, K, max_rounds, leaf_budget)
        order, sorted_lb = _pq_order(lb, K, cap, leaf_budget)
        # the leaf norms as the refine kernel reads them, laid out once
        # before the loop (see search_plan_impl)
        norms = sq_norms.reshape(-1, 1, M) if bk == "pallas" else sq_norms

        # Two accumulators per query:
        #   bsf_d/bsf_e — the LOCAL top-k buffer (never overwritten by
        #          syncs: it is the winner-resolution payload);
        #   pb   — the pruning bound: last PUBLISHED global k-th min
        #          (standard-mode sync).  Pruning/termination use
        #          min(pb, local k-th), eps-scaled in approx mode like
        #          the local plan's cond.
        def bound_of(bsf_d, pb):
            bound = jnp.minimum(pb, bsf_d[:, -1])
            return bound * inv_eps if stop_eps else bound

        def live_anywhere(cursor, bsf_d, pb):
            # (Q,) 1 where the query's next unrefined lower bound beats
            # its bound on some device (the PQ is sorted: slot 0 decides)
            nxt = jax.lax.dynamic_slice_in_dim(sorted_lb, cursor, K, axis=1)
            live = (nxt[:, 0] < bound_of(bsf_d, pb)).astype(jnp.int32)
            return jax.lax.pmax(live, axis)

        def cond(state):
            cursor, live_q = state[0], state[5]
            return jnp.logical_and(cursor < cap * K, jnp.any(live_q > 0))

        def body(state):
            cursor, bsf_d, bsf_e, pb, rounds, live_q, counts = state
            ids = jax.lax.dynamic_slice_in_dim(order, cursor, K, axis=1)
            lbs = jax.lax.dynamic_slice_in_dim(sorted_lb, cursor, K, axis=1)
            alive = lbs < bound_of(bsf_d, pb)[:, None]
            bsf_d, bsf_e = _refine_round(q, q_sq, series, norms, ids, alive,
                                         bsf_d, bsf_e, M=M, k=k, backend=bk,
                                         dma_depth=dd)
            # standard mode: publish the global k-th bound every sync_every
            do_sync = (rounds % sync_every) == (sync_every - 1)
            gbsf = jax.lax.pmin(bsf_d[:, -1], axis)
            pb = jnp.where(do_sync, jnp.minimum(pb, gbsf), pb)
            # live query-rounds, refined pairs, rounds this device worked
            n_alive = jnp.sum(alive, dtype=jnp.int32)
            counts = counts + jnp.stack([jnp.sum(live_q), n_alive,
                                         (n_alive > 0).astype(jnp.int32)])
            return (cursor + K, bsf_d, bsf_e, pb, rounds + 1,
                    live_anywhere(cursor + K, bsf_d, pb), counts)

        bsf_d, pb = jnp.full((Q, k), BIG), jnp.full((Q,), BIG)
        state = (jnp.int32(0), bsf_d, jnp.zeros((Q, k), jnp.int32), pb,
                 jnp.int32(0), live_anywhere(0, bsf_d, pb),
                 jnp.zeros((3,), jnp.int32))
        _, bsf_d, bsf_e, _, rounds, _, (live, refined, worked) = \
            jax.lax.while_loop(cond, body, state)

        # recompute the local winners' distances in DIRECT form (matmul
        # form loses ~1e-3 absolute to f32 cancellation — see search())
        found = bsf_d < BIG
        d_local = jnp.where(found, direct_sq(q, series, bsf_e), bsf_d)
        ids_local = jnp.where(found, perm[bsf_e], -1)

        # final resolution: gather the n_dev local buffers, top-k the union
        all_d = jax.lax.all_gather(d_local, axis)        # (n_dev, Q, k)
        all_i = jax.lax.all_gather(ids_local, axis)
        all_d = jnp.moveaxis(all_d, 0, 1).reshape(Q, -1)
        all_i = jnp.moveaxis(all_i, 0, 1).reshape(Q, -1)
        neg, pos = jax.lax.top_k(-all_d, k)              # ascending
        dist = -neg
        bid = jnp.take_along_axis(all_i, pos, axis=1)
        # rounds and live are replicated (the loop and its liveness are
        # collective); the rest are this device's, summed over devices
        summed = jax.lax.psum(jnp.stack([refined, Q * rounds, worked]), axis)
        counts = jnp.concatenate([jnp.stack([rounds, live]), summed])
        return jnp.sqrt(dist), bid, counts

    pleaf = P(axis, None)
    out2 = P(None, None)

    def sharded_plan_impl(idx: FlatIndex, queries: jnp.ndarray):
        q, q_paa, q_sq = prepare_query_rows(queries, znorm, index=idx)
        fn = shard_map(
            _local_search, mesh=mesh,
            in_specs=(pleaf, P(axis), P(axis), pleaf, pleaf,
                      P(None, None), P(None, None), P(None)),
            out_specs=(out2, out2, P()),
            check_rep=False)
        return fn(idx.series, idx.sq_norms, idx.perm, idx.leaf_lo,
                  idx.leaf_hi, q, q_paa, q_sq)

    return sharded_plan_impl


def build_sharded_search(mesh: Mesh, *, axis: str = "data", k: int = 1,
                         round_leaves: Optional[int] = None,
                         sync_every: int = 1,
                         max_rounds: Optional[int] = None, znorm: bool = True,
                         backend: Optional[str] = None,
                         pq_budget: Optional[int] = None,
                         stop_eps: float = 0.0,
                         stop_leaves: Optional[int] = None,
                         dma_depth: Optional[int] = None,
                         tune=None, config=None):
    """Builds a jitted sharded k-NN `search(idx, queries)` for the mesh.

    The free-function spelling over `build_sharded_plan`, behind the
    deprecated `make_sharded_search`: the pure plan is traced through one
    `jax.jit` (its counters dropped) and the historical k == 1 squeeze is
    applied outside it, so results keep the `FreshIndex.search` shapes
    ((Q,) for k == 1, (Q, k) ascending otherwise).
    """
    plan = jax.jit(build_sharded_plan(
        mesh, axis=axis, k=k, round_leaves=round_leaves,
        sync_every=sync_every, max_rounds=max_rounds, znorm=znorm,
        backend=backend, pq_budget=pq_budget, stop_eps=stop_eps,
        stop_leaves=stop_leaves, dma_depth=dma_depth,
        tune=tune, config=config))

    def sharded_search(idx: FlatIndex, queries: jnp.ndarray):
        d, i, _ = plan(idx, queries)
        return squeeze_k(d, i, k)

    return sharded_search


def make_sharded_search(mesh: Mesh, **kwargs):
    """DEPRECATED free-function spelling of the sharded search builder.

    Compatibility shim over `build_sharded_search`; new code should call
    `FreshIndex.shard(mesh)` and then `index.search(q, k=...)`.
    """
    _warn_deprecated_free_function(
        "make_sharded_search",
        "FreshIndex.shard(mesh) then index.search(q, k=...)")
    return build_sharded_search(mesh, **kwargs)
