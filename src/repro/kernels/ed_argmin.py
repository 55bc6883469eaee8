"""Pallas TPU kernel: refinement — batched Euclidean argmin in matmul form.

The refinement stage is the compute hot spot of query answering: real
distances between Q queries and N candidate series.  Written as

    d2[q, n] = ||q||^2 + ||x_n||^2 - 2 <q, x_n>

the dominant term is a (Q, L) x (L, N) matmul -> the MXU does the heavy
lifting (the paper's SIMD loops become systolic-array work).  The kernel
streams candidate blocks and keeps a running (min, argmin) accumulator per
query, so N can exceed VMEM by any factor with zero extra HBM traffic for
intermediates — the (Q, N) distance matrix is never materialized.

Tiling: grid (Q/BQ, N/BN); N is the inner, sequential ("arbitrary")
dimension so the output tile (BQ, 1) acts as an accumulator revisited by
every j step (initialized at j == 0 via pl.when).  BQ, BN multiples of
8/128; L (=256) lane-aligned.  VMEM per step: q tile BQ*L*4 + x tile
BN*L*4 = 128*256*4 + 512*256*4 ≈ 0.7 MiB.

Numerics: accumulation and the norm epilogue in f32 (inputs may be bf16;
preferred_element_type=f32 on the dot).  Ties: first (lowest-index) winner,
matching jnp.argmin.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ed_kernel(q_ref, x_ref, min_ref, arg_ref, *, block_n: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        min_ref[...] = jnp.full_like(min_ref, 1e30)
        arg_ref[...] = jnp.full_like(arg_ref, -1)

    q = q_ref[...].astype(jnp.float32)            # (BQ, L)
    x = x_ref[...].astype(jnp.float32)            # (BN, L)
    q_sq = jnp.sum(q * q, axis=1, keepdims=True)  # (BQ, 1)
    x_sq = jnp.sum(x * x, axis=1)[None, :]        # (1, BN)
    dots = jax.lax.dot_general(q, x, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    d2 = jnp.maximum(q_sq + x_sq - 2.0 * dots, 0.0)          # (BQ, BN)

    loc = jnp.argmin(d2, axis=1)                             # (BQ,)
    dmin = jnp.min(d2, axis=1)[:, None]                      # (BQ, 1)
    gidx = (j * block_n + loc).astype(jnp.int32)[:, None]    # (BQ, 1)

    cur = min_ref[...]
    upd = dmin < cur
    min_ref[...] = jnp.where(upd, dmin, cur)
    arg_ref[...] = jnp.where(upd, gidx, arg_ref[...])


@functools.partial(jax.jit, static_argnames=("block_q", "block_n",
                                             "interpret"))
def ed_argmin(q: jnp.ndarray, xs: jnp.ndarray, *, block_q: int = 128,
              block_n: int = 512, interpret: bool = None):
    """q: (Q, L), xs: (N, L) -> ((Q,) min d^2 f32, (Q,) argmin i32).

    interpret=None resolves via _compat.resolve_interpret (Mosaic on TPU).
    """
    from ._compat import resolve_interpret
    interpret = resolve_interpret(interpret)
    Q, L = q.shape
    N = xs.shape[0]
    bq = min(block_q, max(8, Q))
    bn = min(block_n, max(8, N))
    Qp = -(-Q // bq) * bq
    Np = -(-N // bn) * bn
    q = jnp.pad(q.astype(jnp.float32), ((0, Qp - Q), (0, 0)))
    # pad candidates far away so they never win the min
    xs = jnp.pad(xs.astype(jnp.float32), ((0, Np - N), (0, 0)),
                 constant_values=1e10)

    dmin, arg = pl.pallas_call(
        functools.partial(_ed_kernel, block_n=bn),
        grid=(Qp // bq, Np // bn),
        in_specs=[
            pl.BlockSpec((bq, L), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, L), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Qp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Qp, 1), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(q, xs)
    return dmin[:Q, 0], arg[:Q, 0]
