"""Pallas TPU kernel: fused z-normalize + PAA + iSAX quantization.

The buffer-creation stage is bandwidth-bound: each series is read once and
reduced 16x (L=256 -> w=16 PAA values) then 32x further (f32 -> 8-bit
symbol).  Fusing z-norm + PAA + quantization into one pass means the series
leaves HBM exactly once — the arithmetic (a few fused reductions + 2^bits-1
compares against the breakpoint table) is free next to the memory stream.

Tiling: grid over row blocks of BN series; each block holds a (BN, L) f32
tile in VMEM (BN=256, L=256 -> 256 KiB, comfortably inside the ~16 MiB v5e
VMEM even with double buffering).  L is a multiple of 128 => lane-aligned.
Outputs are written TRANSPOSED, as (w, BN) tiles of (w, n) arrays: series
run along the lanes.  An (n, w) output would be padded from w=16 to 128
lanes in HBM — 2 GiB per output at n = 2^22 instead of 256 MiB.

z-normalization (optional) uses isax.znormalize's formula: mean, then the
root mean squared deviation, then (x - mu) / (sd + 1e-8).  PAA is the
(w, L) x (BN, L)^T matmul against a constant averaging matrix (1/seg on
the segment's columns), at HIGHEST precision so it keeps f32 accuracy on
the MXU.  The breakpoint table (2^bits - 1 scalars) rides in SMEM;
quantization is sum_b [paa > bp_b], one (w, BN) compare-add per
breakpoint, replacing the host searchsorted.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import isax


def _summarize_kernel(x_ref, avg_ref, bp_ref, paa_ref, word_ref, *,
                      znorm: bool):
    x = x_ref[...].astype(jnp.float32)            # (BN, L)
    if znorm:
        mu = jnp.mean(x, axis=1, keepdims=True)
        x = x - mu
        sd = jnp.sqrt(jnp.mean(x * x, axis=1, keepdims=True))
        x = x / (sd + 1e-8)
    # PAA as one matmul against the averaging matrix (Mosaic cannot split
    # the lane dim into (w, L/w) for a segment mean), series on the lanes
    p = jax.lax.dot_general(avg_ref[...], x, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)  # (w, BN)
    paa_ref[...] = p
    # symbol = #breakpoints strictly below the PAA value
    sym = jnp.zeros(p.shape, jnp.int32)
    for b in range(bp_ref.shape[1]):       # static unroll, (w, BN) each
        sym = sym + (p > bp_ref[0, b]).astype(jnp.int32)
    word_ref[...] = sym


@functools.partial(jax.jit, static_argnames=("segments", "bits", "znorm",
                                             "block_rows", "interpret"))
def summarize(x: jnp.ndarray, *, segments: int = isax.SEGMENTS,
              bits: int = isax.SAX_BITS, znorm: bool = True,
              block_rows: int = 256, interpret: bool = None):
    """x: (n, L) -> (paa (n, w) f32, words (n, w) i32).  Pads n internally.

    interpret=None resolves via _compat.resolve_interpret (Mosaic on TPU).
    """
    from ._compat import resolve_interpret
    interpret = resolve_interpret(interpret)
    n, L = x.shape
    assert L % segments == 0
    bn = min(block_rows, max(8, n))
    n_pad = -(-n // bn) * bn
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)), constant_values=1.0)
    bp = jnp.asarray(isax.breakpoints(bits), jnp.float32)[None, :]
    seg = L // segments
    avg = jnp.asarray(np.repeat(np.eye(segments, dtype=np.float32), seg,
                                axis=1) / seg)                # (w, L)

    grid = (n_pad // bn,)
    paa, words = pl.pallas_call(
        functools.partial(_summarize_kernel, znorm=znorm),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, L), lambda i: (i, 0)),
            pl.BlockSpec((segments, L), lambda i: (0, 0)),
            pl.BlockSpec((1, (1 << bits) - 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((segments, bn), lambda i: (0, i)),
            pl.BlockSpec((segments, bn), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((segments, n_pad), jnp.float32),
            jax.ShapeDtypeStruct((segments, n_pad), jnp.int32),
        ],
        interpret=interpret,
    )(x, avg, bp)
    return paa[:, :n].T, words[:, :n].T
