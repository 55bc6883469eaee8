"""Pallas TPU kernel: batched lower-bound (MINDIST) distances.

The pruning stage evaluates MINDIST(Q, leaf-region) for every (query, leaf)
pair — (Q, NL, w) work that on the original CPU index is a pointer-chasing
tree walk, and here is one dense vectorized sweep (DESIGN.md §2: the SING
move).  Per segment: max(lo - q, 0) + max(q - hi, 0), squared, summed over
w, scaled by L/w.

Tiling: grid (Q/BQ, NL/BL).  Per block: q tile (BQ, w), lo/hi tiles
(w, BL) — the leaf regions ride in TRANSPOSED, so segment s of every leaf
is one lane-dense (1, BL) row — and output tile (BQ, BL).  The (BQ, BL, w)
broadcast intermediate would be 2 MiB f32 at BQ=128, BL=256, w=16, so the
kernel loops over segments with an accumulator instead — w is tiny and
static, so a Python loop unrolls into 16 fused multiply-adds over (BQ, BL)
tiles, each broadcasting a (BQ, 1) query column against a (1, BL) leaf
row.  (Slicing segment s as a column of a (BL, w) tile and turning it
into a lane row is a transpose per segment, which Mosaic's compiler did
not finish compiling.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import isax

#: the kernel's name in the compiled program and a device trace
#: (`%lb_distance.<n>`), whatever the enclosing Python function is named
KERNEL_NAME = "lb_distance"


def _lb_kernel(q_ref, lo_ref, hi_ref, out_ref, *, scale: float):
    w = q_ref.shape[1]        # q (BQ, w); lo/hi (w, BL)
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for s in range(w):        # static unroll: w fused (BQ, BL) FMAs
        qs = q_ref[:, pl.ds(s, 1)]      # (BQ, 1)
        los = lo_ref[pl.ds(s, 1), :]    # (1, BL)
        his = hi_ref[pl.ds(s, 1), :]
        d = jnp.maximum(los - qs, 0.0) + jnp.maximum(qs - his, 0.0)
        acc = acc + d * d
    out_ref[...] = acc * scale


@functools.partial(jax.jit, static_argnames=("series_len", "block_q",
                                             "block_l", "interpret"))
def lb_distance(q_paa: jnp.ndarray, leaf_lo: jnp.ndarray,
                leaf_hi: jnp.ndarray, *, series_len: int = isax.SERIES_LEN,
                block_q: int = 128, block_l: int = 256,
                interpret: bool = None) -> jnp.ndarray:
    """(Q, w) x (NL, w) -> (Q, NL) squared lower bounds.

    interpret=None resolves via _compat.resolve_interpret (Mosaic on TPU,
    interpreter elsewhere) — a hard-coded True would silently run the
    Python interpreter for direct callers even on TPU.
    """
    from ._compat import resolve_interpret
    interpret = resolve_interpret(interpret)
    Q, w = q_paa.shape
    NL = leaf_lo.shape[0]
    bq = min(block_q, max(8, Q))
    bl = min(block_l, max(8, NL))
    Qp = -(-Q // bq) * bq
    NLp = -(-NL // bl) * bl
    q_paa = jnp.pad(q_paa.astype(jnp.float32), ((0, Qp - Q), (0, 0)))
    # pad leaves with an empty region at +inf => lb=+inf, never a candidate
    big = jnp.float32(1e30)
    leaf_lo = jnp.pad(leaf_lo.astype(jnp.float32), ((0, NLp - NL), (0, 0)),
                      constant_values=big)
    leaf_hi = jnp.pad(leaf_hi.astype(jnp.float32), ((0, NLp - NL), (0, 0)),
                      constant_values=big)
    # clamp infinities (inf - inf = nan inside the kernel's FMA form)
    leaf_lo = jnp.clip(leaf_lo, -big, big).T             # (w, NLp)
    leaf_hi = jnp.clip(leaf_hi, -big, big).T

    out = pl.pallas_call(
        functools.partial(_lb_kernel, scale=float(series_len) / w),
        grid=(Qp // bq, NLp // bl),
        in_specs=[
            pl.BlockSpec((bq, w), lambda i, j: (i, 0)),
            pl.BlockSpec((w, bl), lambda i, j: (0, j)),
            pl.BlockSpec((w, bl), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bq, bl), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Qp, NLp), jnp.float32),
        name=KERNEL_NAME,
        interpret=interpret,
    )(q_paa, leaf_lo, leaf_hi)
    return out[:Q, :NL]
