"""Pallas TPU kernel: fused refinement round — gather + distances + prune
+ top-k fold, allocation-free.

One refinement round of the k-NN search visits, for every query, the next
K best leaves of its priority queue and folds the real distances of their
K*M member series into the per-query best-so-far (BSF) top-k buffer.  The
reference path materializes the gathered member rows as a (Q, K*M, L)
tensor in HBM before the matmul ever sees it — at Q=128, K=8, M=64, L=256
that is 64 MiB f32 of pure intermediate traffic per round, dwarfing the
useful reads.  This kernel fuses the whole round:

    gather leaf block -> squared distances (matmul form, MXU)
        -> lower-bound/BSF pruning mask -> rank-select top-k fold

so the only HBM traffic is the leaf blocks themselves (read once, (M, L)
at a time, contiguous — the locality the PQ sort bought us) and the tiny
(Q, k) BSF buffers.  The (Q, K*M, L) intermediate never exists.

Grid and gather: grid (Q, K) — one program per (query row, PQ slot).  The
leaf visited by program (i, j) is data-dependent (`leaf_ids[i, j]`), so the
ids ride in as a scalar-prefetch operand and the series BlockSpec
index_map reads them to DMA exactly the addressed (M, L) leaf block into
VMEM (the paged-attention move).  j is the inner, sequential grid
dimension: the (1, kp) output tiles act as accumulators revisited by every
j step (initialized from the carried-in BSF at j == 0, exactly like
ed_argmin's running min).

Pruning: `alive[i, j]` (precomputed outside from lb vs the round-start
k-th BSF — O(Q*K), free) also rides in scalar-prefetch; a dead (query,
leaf) program skips gather arithmetic via pl.when, AND skips the HBM->VMEM
copy itself: the wrapper forward-fills dead PQ slots with the last alive
slot's leaf id, so the pipeliner sees an unchanged block index across the
dead steps and elides the DMA (late rounds, where most queries are already
finished, then stream no pruned leaf bytes at all).  Skipping is
bit-identical to the reference path's where(alive, d2, BIG) masking: a
masked candidate carries distance BIG and can never displace a buffer slot
(ties prefer the lower union index, and buffer slots precede candidates),
and dead programs never read the (possibly stale) block.

Top-k fold without a sort: the union of the kp carried slots and the M
candidates is ranked by a (U, U) comparison matrix — rank(e) = #{f :
d_f < d_e or (d_f == d_e and f < e)} — a total order, so slot t of the
output is the unique union element of rank t, selected by a one-hot
sum.  U = kp + M is tiny (~74 at k=10, M=64); the O(U^2) compare-reduce
vectorizes on the VPU and needs no jax.lax.sort lowering inside Mosaic.
The index tie-break reproduces jax.lax.top_k's lower-index preference, so
the fold is bit-comparable with the reference merge in ref.refine_topk_ref
(same final buffer CONTENTS and ORDER — see tests/test_refine.py).

Buffer width: kp = k in interpret mode; on Mosaic the buffer is padded up
to a 128-lane multiple (padded slots carry d=BIG, entry 0 — they sort
after every real candidate and are sliced off by the wrapper).

Block layout: the per-query operands ride in as (Q, 1, L), (Q, 1, 1) and
(Q, 1, kp) arrays and the leaf norms as (NL, 1, M), with the leading dim
squeezed out of each block.  Mosaic requires a block's last two dims to
be (8, 128)-divisible or equal to the array's; a (1, L) row block of a
(Q, L) array is neither, while the last two dims of these 3-D blocks are
the whole trailing array dims.  The kernel body still sees (1, L) /
(1, kp) / (1, M) rows.

Structures: the round has two Mosaic kernel structures behind one
wrapper, selected by `dma_depth` and tuned by `kernels.autotune`:

  dma_depth=1   the grid-(Q, K) scalar-prefetch kernel above — the
                BlockSpec pipeliner double-buffers the leaf copies
                implicitly (one block look-ahead);
  dma_depth>=2  `series` stays in HBM (`pltpu.HBM`) and the kernel
                issues its own `make_async_copy` chain into a
                (depth, M, L) VMEM ring: the copy for PQ slot j+depth-1
                is IN FLIGHT while slot j computes, and a pruned slot
                starts no copy at all (the explicit form of the
                forward-fill DMA elision).  Bit-identical fold, deeper
                overlap for leaves whose DMA latency exceeds one round of
                compute.

Both structures run under interpret mode on CPU, which is how CI
exercises them without the hardware.  Exactness contract: the default
structure is bit-identical to ref.refine_topk_ref (asserted by the test
suite); the DMA-ring variant returns exactly the same ENTRIES in the
same order, with distances equal to the last ulp or so — XLA's dot
merger batches a program's unrolled per-slot dots into one larger dot
whose tail-lane reduction can differ by 1 ulp from the one-dot-per-
program default.  The autotune sweep therefore gates every candidate
config on BITWISE equality against the default-knob output on the live
device (kernels/autotune.py): a variant structure only ever reaches the
tuned table where it is provably bit-identical there.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._compat import resolve_lowering

BIG = 1e30

#: the name both structures give their Mosaic kernel, so the compiled
#: program (and a device trace) calls it `%refine_topk.<n>` whatever the
#: enclosing Python function is named
KERNEL_NAME = "refine_topk"


def _rank_select(u_d: jnp.ndarray, u_e: jnp.ndarray, kp: int
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(1, U) distances + (1, U) entries -> the kp smallest, ascending.

    rank(e) = #{f : d_f < d_e or (d_f == d_e and f < e)} is a permutation
    of 0..U-1 (the index term breaks every tie), so `rank == t` selects
    exactly one element per output slot.
    """
    U = u_d.shape[1]
    dcol = jnp.reshape(u_d, (U, 1))                    # d_f down the rows
    drow = u_d                                         # d_e along the lanes
    fcol = jax.lax.broadcasted_iota(jnp.int32, (U, U), 0)
    frow = jax.lax.broadcasted_iota(jnp.int32, (U, U), 1)
    smaller = (dcol < drow) | ((dcol == drow) & (fcol < frow))
    rank = jnp.sum(smaller.astype(jnp.int32), axis=0)  # (U,) rank of elem e
    slot = jax.lax.broadcasted_iota(jnp.int32, (U, kp), 1)
    onehot = rank[:, None] == slot                     # (U, kp)
    out_d = jnp.sum(jnp.where(onehot, jnp.reshape(u_d, (U, 1)), 0.0), axis=0)
    out_e = jnp.sum(jnp.where(onehot, jnp.reshape(u_e, (U, 1)), 0), axis=0)
    return out_d[None, :], out_e[None, :]


def _refine_kernel(ids_ref, alive_ref, q_ref, qsq_ref, bsfd_ref, bsfe_ref,
                   xs_ref, xn_ref, outd_ref, oute_ref, *,
                   leaf_capacity: int, kp: int):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():                       # seed the accumulator from the carry
        outd_ref[...] = bsfd_ref[...]
        oute_ref[...] = bsfe_ref[...]

    @pl.when(alive_ref[i, j] != 0)
    def _fold():
        M = leaf_capacity
        q = q_ref[...].astype(jnp.float32)             # (1, L)
        xs = xs_ref[...].astype(jnp.float32)           # (M, L) leaf block
        xn = xn_ref[...]                               # (1, M)
        dots = jax.lax.dot_general(q, xs, (((1,), (1,)), ((), ())),
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)
        d2 = jnp.maximum(qsq_ref[...] + xn - 2.0 * dots, 0.0)   # (1, M)
        cand_e = (ids_ref[i, j] * M
                  + jax.lax.broadcasted_iota(jnp.int32, (1, M), 1))
        u_d = jnp.concatenate([outd_ref[...], d2], axis=1)       # (1, kp+M)
        u_e = jnp.concatenate([oute_ref[...], cand_e], axis=1)
        outd_ref[...], oute_ref[...] = _rank_select(u_d, u_e, kp)


def _refine_kernel_dma(ids_ref, alive_ref, q_ref, qsq_ref, bsfd_ref,
                       bsfe_ref, xs_hbm, xn_hbm, outd_ref, oute_ref,
                       xs_buf, xn_buf, xs_sem, xn_sem, *,
                       leaf_capacity: int, kp: int, depth: int,
                       n_slots: int):
    """Mosaic structure, explicit DMA ring: grid (Q,) — one program per
    query row walks its K PQ slots (statically unrolled), keeping up to
    `depth` leaf copies (HBM -> VMEM ring buffer) in flight ahead of the
    compute slot.  A pruned slot never starts a copy (explicit DMA
    elision; no forward-fill needed), and the fold under the wait is the
    same _rank_select as the pipelined kernel — bit-identical results.
    """
    i = pl.program_id(0)
    M = leaf_capacity

    outd_ref[...] = bsfd_ref[...]
    oute_ref[...] = bsfe_ref[...]

    # the slot walk is unrolled (n_slots is static and small — it is
    # round_leaves): slot indices into the ring are static, and the
    # per-slot dot is the same straight-line op as the pipelined kernel's
    # (bit-identical accumulation — a fori_loop-wrapped dot may compile
    # to a different reduction order)
    def start(j):
        if j >= n_slots:                   # ring warmup past the last slot
            return
        slot = j % depth

        @pl.when(alive_ref[i, j] != 0)     # pruned slot: no copy at all
        def _():
            pltpu.make_async_copy(
                xs_hbm.at[pl.ds(ids_ref[i, j] * M, M), :],
                xs_buf.at[slot], xs_sem.at[slot]).start()
            pltpu.make_async_copy(
                xn_hbm.at[pl.ds(ids_ref[i, j], 1)],
                xn_buf.at[pl.ds(slot, 1)], xn_sem.at[slot]).start()

    for warm in range(depth - 1):          # fill the ring ahead of slot 0
        start(warm)

    for j in range(n_slots):
        start(j + depth - 1)               # keep `depth` copies in flight
        slot = j % depth

        @pl.when(alive_ref[i, j] != 0)
        def _fold(j=j, slot=slot):
            pltpu.make_async_copy(
                xs_hbm.at[pl.ds(ids_ref[i, j] * M, M), :],
                xs_buf.at[slot], xs_sem.at[slot]).wait()
            pltpu.make_async_copy(
                xn_hbm.at[pl.ds(ids_ref[i, j], 1)],
                xn_buf.at[pl.ds(slot, 1)], xn_sem.at[slot]).wait()
            q = q_ref[...].astype(jnp.float32)             # (1, L)
            xs = xs_buf[slot].astype(jnp.float32)          # (M, L)
            xn = xn_buf[slot][:, :M]                       # (1, M)
            dots = jax.lax.dot_general(q, xs, (((1,), (1,)), ((), ())),
                                       precision=jax.lax.Precision.HIGHEST,
                                       preferred_element_type=jnp.float32)
            d2 = jnp.maximum(qsq_ref[...] + xn - 2.0 * dots, 0.0)
            cand_e = (ids_ref[i, j] * M
                      + jax.lax.broadcasted_iota(jnp.int32, (1, M), 1))
            u_d = jnp.concatenate([outd_ref[...], d2], axis=1)
            u_e = jnp.concatenate([oute_ref[...], cand_e], axis=1)
            outd_ref[...], oute_ref[...] = _rank_select(u_d, u_e, kp)


def _leaf_norms(sq_norms, M: int, lanes: int = 0):
    """(n_pad,) or (NL, 1, M) f32 norms -> (NL, 1, max(M, lanes)): one
    row per leaf, whose block's last two dims equal the array's (see
    Block layout).  `lanes` zero-pads each row: an HBM array is tiled in
    128-lane rows, and a DMA may only slice whole tiles out of it."""
    xn = sq_norms.astype(jnp.float32).reshape(-1, 1, M)
    if lanes > M:
        xn = jnp.pad(xn, ((0, 0), (0, 0), (0, lanes - M)))
    return xn


def _refine_mosaic(q, q_sq, series, sq_norms, ids32, alive32, bsf_d, bsf_e,
                   *, M: int, kp: int, interpret: bool):
    """dma_depth == 1: the scalar-prefetch grid-(Q, K) kernel with the
    BlockSpec pipeliner's implicit double-buffering + forward-fill DMA
    elision."""
    Q, L = q.shape
    K = ids32.shape[1]
    # DMA elision for pruned slots: a dead slot repeats the last alive
    # slot's leaf id (slot 0's id when the row starts dead — that block is
    # fetched at j == 0 regardless), so consecutive grid steps address the
    # same block and the pipeliner skips the copy.  Dead programs never
    # read the block, and alive slots keep their own id (the forward fill
    # maps an alive slot to itself), so results are unchanged.
    slot = jnp.arange(K, dtype=jnp.int32)[None, :]
    last_alive = jax.lax.cummax(jnp.where(alive32 != 0, slot, -1), axis=1)
    ids32 = jnp.take_along_axis(ids32, jnp.maximum(last_alive, 0), axis=1)

    row = lambda i, j, ids, al: (i, 0, 0)              # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # leaf ids + alive mask
        grid=(Q, K),                           # j (PQ slot) innermost
        in_specs=[
            pl.BlockSpec((None, 1, L), row),
            pl.BlockSpec((None, 1, 1), row),
            pl.BlockSpec((None, 1, kp), row),
            pl.BlockSpec((None, 1, kp), row),
            # the data-dependent gather: block row = the addressed leaf
            pl.BlockSpec((M, L), lambda i, j, ids, al: (ids[i, j], 0)),
            pl.BlockSpec((None, 1, M),
                         lambda i, j, ids, al: (ids[i, j], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, kp), row),
            pl.BlockSpec((None, 1, kp), row),
        ],
    )
    out_d, out_e = pl.pallas_call(
        functools.partial(_refine_kernel, leaf_capacity=M, kp=kp),
        grid_spec=grid_spec,
        name=KERNEL_NAME,
        out_shape=[
            jax.ShapeDtypeStruct((Q, 1, kp), jnp.float32),
            jax.ShapeDtypeStruct((Q, 1, kp), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(ids32, alive32, q[:, None, :], q_sq[:, None, None], bsf_d[:, None, :],
      bsf_e[:, None, :], series, _leaf_norms(sq_norms, M))
    return out_d[:, 0, :], out_e[:, 0, :]


def _refine_mosaic_dma(q, q_sq, series, sq_norms, ids32, alive32, bsf_d,
                       bsf_e, *, M: int, kp: int, depth: int,
                       interpret: bool):
    """dma_depth >= 2: series stays in HBM (pltpu.HBM) and the kernel
    drives its own `depth`-deep make_async_copy ring."""
    Q, L = q.shape
    K = ids32.shape[1]
    Mp = -(-M // 128) * 128                 # norm rows in whole lane tiles

    row = lambda i, ids, al: (i, 0, 0)                 # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Q,),
        in_specs=[
            pl.BlockSpec((None, 1, L), row),
            pl.BlockSpec((None, 1, 1), row),
            pl.BlockSpec((None, 1, kp), row),
            pl.BlockSpec((None, 1, kp), row),
            pl.BlockSpec(memory_space=pltpu.HBM),      # series: stay in HBM
            pl.BlockSpec(memory_space=pltpu.HBM),      # leaf norms
        ],
        out_specs=[
            pl.BlockSpec((None, 1, kp), row),
            pl.BlockSpec((None, 1, kp), row),
        ],
        scratch_shapes=[
            # ring in the STORED dtype — the copy moves leaf bytes as-is
            # (bf16 leaves stream at bf16 width); the fold casts to f32
            pltpu.VMEM((depth, M, L), series.dtype),   # leaf block ring
            pltpu.VMEM((depth, 1, Mp), jnp.float32),   # leaf norm ring
            pltpu.SemaphoreType.DMA((depth,)),
            pltpu.SemaphoreType.DMA((depth,)),
        ],
    )
    out_d, out_e = pl.pallas_call(
        functools.partial(_refine_kernel_dma, leaf_capacity=M, kp=kp,
                          depth=depth, n_slots=K),
        grid_spec=grid_spec,
        name=KERNEL_NAME,
        out_shape=[
            jax.ShapeDtypeStruct((Q, 1, kp), jnp.float32),
            jax.ShapeDtypeStruct((Q, 1, kp), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(ids32, alive32, q[:, None, :], q_sq[:, None, None], bsf_d[:, None, :],
      bsf_e[:, None, :], series, _leaf_norms(sq_norms, M, Mp))
    return out_d[:, 0, :], out_e[:, 0, :]


@functools.partial(jax.jit, static_argnames=("leaf_capacity", "k",
                                             "interpret", "dma_depth",
                                             "lowering"))
def refine_topk(q: jnp.ndarray, q_sq: jnp.ndarray, series: jnp.ndarray,
                sq_norms: jnp.ndarray, leaf_ids: jnp.ndarray,
                alive: jnp.ndarray, bsf_d: jnp.ndarray, bsf_e: jnp.ndarray,
                *, leaf_capacity: int, k: int,
                interpret: Optional[bool] = None,
                dma_depth: int = 1,
                lowering: Optional[str] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One fused refinement round.

    q:        (Q, L) f32 prepared queries
    q_sq:     (Q,)   f32 ||q||^2
    series:   (n_pad, L) leaf-ordered series (any float dtype; math in f32)
    sq_norms: (n_pad,)   f32 ||x||^2 (padded rows pushed to 1e30), or
              the same as (n_pad // leaf_capacity, 1, leaf_capacity)
              rows, the layout the kernel reads (a caller that runs many
              rounds lays it out once)
    leaf_ids: (Q, K) i32 leaves to visit this round (PQ order)
    alive:    (Q, K) bool/int — lb < round-start k-th BSF (pruning mask)
    bsf_d/e:  (Q, k) carried top-k buffer (ascending) / entry ids
    dma_depth: 1 uses the pipelined BlockSpec kernel; >= 2 the explicit
              `depth`-deep DMA-ring kernel.
    lowering: None or 'mosaic' (the only structure family); anything
              else raises ValueError.
    -> the merged (Q, k) buffer, same semantics as the reference
       ref.refine_topk_ref round, with no (Q, K*M, L) intermediate.
       Every dma_depth returns the same entries in the same order; the
       default structure is additionally bit-identical in distances
       (see the module docstring's exactness contract).
    """
    _, interpret = resolve_lowering(interpret, lowering)
    if dma_depth < 1:
        raise ValueError(f"dma_depth must be >= 1, got {dma_depth}")
    K = leaf_ids.shape[1]
    M = leaf_capacity
    if interpret:
        kp = k                      # exact width in interpret mode
    else:
        kp = -(-k // 128) * 128     # lane-pad the buffer on Mosaic
    if kp != k:
        bsf_d = jnp.pad(bsf_d, ((0, 0), (0, kp - k)), constant_values=BIG)
        bsf_e = jnp.pad(bsf_e, ((0, 0), (0, kp - k)))

    ids32 = leaf_ids.astype(jnp.int32)
    alive32 = alive.astype(jnp.int32)

    if dma_depth >= 2 and K >= 2:
        out_d, out_e = _refine_mosaic_dma(
            q, q_sq, series, sq_norms, ids32, alive32, bsf_d, bsf_e,
            M=M, kp=kp, depth=min(dma_depth, K), interpret=interpret)
    else:
        out_d, out_e = _refine_mosaic(
            q, q_sq, series, sq_norms, ids32, alive32, bsf_d, bsf_e,
            M=M, kp=kp, interpret=interpret)
    return out_d[:, :k], out_e[:, :k]
