"""Core iSAX math: z-normalization, PAA, iSAX words, distances.

This module is the numeric foundation of FreSh (Section II of the paper):

  * PAA(x)      — Piecewise Aggregate Approximation: w segment means.
  * iSAX(x)     — per-segment symbol = index of the N(0,1) quantile region the
                  PAA value falls into, at a maximum cardinality 2^SAX_BITS.
  * MINDIST     — the *lower-bound distance* between a query and an iSAX
                  summary/region.  Satisfies the pruning property
                  MINDIST(Q, iSAX(X)) <= ED(Q, X), which is what makes index
                  pruning sound.
  * ED          — real (Euclidean) distance.

Everything is pure jnp (differentiability is irrelevant here, but purity and
jit-ability are) with a small numpy path for host-side breakpoint tables.

The N(0,1) quantiles (SAX "breakpoints") are computed with Acklam's rational
approximation of the inverse normal CDF (|rel.err| < 1.15e-9) so we do not
depend on scipy (not installed in this environment).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Defaults (match the paper's experimental setup: series of length 256,
# w = 16 segments, 8-bit symbols => up to 2^16 root subtrees via first bits).
# ---------------------------------------------------------------------------
SERIES_LEN = 256
SEGMENTS = 16
SAX_BITS = 8
CARDINALITY = 1 << SAX_BITS  # 256


# ---------------------------------------------------------------------------
# Inverse normal CDF (Acklam).  Host-side, numpy.
# ---------------------------------------------------------------------------
_ACKLAM_A = (-3.969683028665376e+01, 2.209460984245205e+02,
             -2.759285104469687e+02, 1.383577518672690e+02,
             -3.066479806614716e+01, 2.506628277459239e+00)
_ACKLAM_B = (-5.447609879822406e+01, 1.615858368580409e+02,
             -1.556989798598866e+02, 6.680131188771972e+01,
             -1.328068155288572e+01)
_ACKLAM_C = (-7.784894002430293e-03, -3.223964580411365e-01,
             -2.400758277161838e+00, -2.549732539343734e+00,
             4.374664141464968e+00, 2.938163982698783e+00)
_ACKLAM_D = (7.784695709041462e-03, 3.224671290700398e-01,
             2.445134137142996e+00, 3.754408661907416e+00)


def ndtri(p: np.ndarray) -> np.ndarray:
    """Inverse standard-normal CDF (Acklam's approximation), numpy host-side."""
    p = np.asarray(p, dtype=np.float64)
    out = np.empty_like(p)
    a, b, c, d = _ACKLAM_A, _ACKLAM_B, _ACKLAM_C, _ACKLAM_D
    plow, phigh = 0.02425, 1.0 - 0.02425

    lo = p < plow
    hi = p > phigh
    mid = ~(lo | hi)

    if np.any(lo):
        q = np.sqrt(-2.0 * np.log(p[lo]))
        out[lo] = ((((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q + c[5])
                   / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1.0))
    if np.any(hi):
        q = np.sqrt(-2.0 * np.log(1.0 - p[hi]))
        out[hi] = -((((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q + c[5])
                    / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1.0))
    if np.any(mid):
        q = p[mid] - 0.5
        r = q * q
        out[mid] = ((((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r + a[5]) * q
                    / (((((b[0] * r + b[1]) * r + b[2]) * r + b[3]) * r + b[4]) * r + 1.0))
    return out


@functools.lru_cache(maxsize=None)
def breakpoints(bits: int = SAX_BITS) -> np.ndarray:
    """The 2^bits - 1 interior N(0,1) quantile breakpoints, ascending (np.f64)."""
    card = 1 << bits
    return ndtri(np.arange(1, card) / card)


@functools.lru_cache(maxsize=None)
def padded_breakpoints(bits: int = SAX_BITS) -> np.ndarray:
    """Breakpoints padded with -inf / +inf: region of symbol v is
    [pad[v], pad[v + 1]].  Length 2^bits + 1."""
    bp = breakpoints(bits)
    return np.concatenate([[-np.inf], bp, [np.inf]])


# ---------------------------------------------------------------------------
# Series transforms (jnp, jit-safe)
# ---------------------------------------------------------------------------
def znormalize(x: jnp.ndarray, eps: float = 1e-8) -> jnp.ndarray:
    """Per-series z-normalization over the last axis (paper's preprocessing)."""
    mu = jnp.mean(x, axis=-1, keepdims=True)
    sd = jnp.std(x, axis=-1, keepdims=True)
    return (x - mu) / (sd + eps)


def paa(x: jnp.ndarray, segments: int = SEGMENTS) -> jnp.ndarray:
    """Piecewise Aggregate Approximation: mean over each of `segments` equal
    slices of the last axis.  x: (..., n) -> (..., segments)."""
    n = x.shape[-1]
    assert n % segments == 0, f"series length {n} not divisible by w={segments}"
    return jnp.mean(x.reshape(*x.shape[:-1], segments, n // segments), axis=-1)


def sax_word(paa_vals: jnp.ndarray, bits: int = SAX_BITS) -> jnp.ndarray:
    """Quantize PAA values into iSAX symbols at max cardinality.

    symbol = #breakpoints strictly below the value = searchsorted index.
    Output dtype uint8 (bits <= 8) / int32 otherwise.
    """
    bp = jnp.asarray(breakpoints(bits), dtype=paa_vals.dtype)
    sym = jnp.searchsorted(bp, paa_vals, side="right")
    dtype = jnp.uint8 if bits <= 8 else jnp.int32
    return sym.astype(dtype)


def summarize(x: jnp.ndarray, segments: int = SEGMENTS,
              bits: int = SAX_BITS) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full summarization stage: series -> (paa, isax_word)."""
    p = paa(x, segments)
    return p, sax_word(p, bits)


def root_bucket(words: jnp.ndarray, bits: int = SAX_BITS) -> jnp.ndarray:
    """First-bit signature: MSB of each segment's symbol, packed into an int.

    This is how iSAX indexes route a series into one of 2^w summarization
    buffers / root subtrees (Section V-A of the paper).
    words: (..., w) uint8 -> (...,) int32 in [0, 2^w).
    """
    w = words.shape[-1]
    msb = (words >> (bits - 1)).astype(jnp.int32)  # (..., w) in {0, 1}
    weights = (1 << jnp.arange(w - 1, -1, -1, dtype=jnp.int32))
    return jnp.sum(msb * weights, axis=-1)


def interleaved_key(words: jnp.ndarray, bits: int = SAX_BITS) -> jnp.ndarray:
    """Round-robin bit-interleaved sort key.

    Take bit (bits-1) of every segment (MSB first), then bit (bits-2) of every
    segment, ...  Sorting by this key orders series exactly as the leaves of a
    balanced iSAX tree that splits segments round-robin, one extra bit at a
    time — i.e. the flat-array equivalent of the paper's leaf-oriented tree.

    words: (..., w) -> (..., n_lanes) int32 lanes of 31 key bits each
    (w=16, bits=8 -> 128 key bits -> 5 lanes); lexicographic comparison of
    the lane tuple equals comparison of the full 128-bit key.
    """
    w = words.shape[-1]
    total = w * bits
    bitpos = []
    for b in range(bits - 1, -1, -1):  # MSB plane first
        for s in range(w):
            bitpos.append((s, b))
    # bit i (0 = most significant) of the key comes from segment s, bit b;
    # pack into ceil(total/31) int32 lanes (31 bits per lane keeps sign bit
    # 0; int64 is unavailable without jax_enable_x64).  Each lane is built
    # by shift-and-or on (...,) arrays, never as a (..., total) bit matrix
    wi = words.astype(jnp.int32)
    lanes = []
    for lane_start in range(0, total, 31):
        chunk = bitpos[lane_start:lane_start + 31]
        acc = jnp.zeros(words.shape[:-1], jnp.int32)
        for pos, (s, b) in enumerate(chunk):
            bit = (wi[..., s] >> b) & 1
            acc = acc | (bit << (len(chunk) - 1 - pos))
        lanes.append(acc)
    return jnp.stack(lanes, axis=-1)  # (..., n_lanes)


def lexsort_lanes(key: jnp.ndarray) -> jnp.ndarray:
    """Stable ascending order of (n, n_lanes) keys, lane 0 primary — the
    device twin of `lexsort_keys` (ties keep their input order).

    One stable single-key sort per lane, least significant lane first
    (an LSD radix sort over lanes) inside a fori_loop: the TPU compiler
    takes minutes on one sort with n_lanes keys, and seconds on this."""
    n, n_lanes = key.shape
    lanes = key.T                                       # (n_lanes, n)
    iota = jnp.arange(n, dtype=jnp.int32)

    def one_lane(j, perm):
        k = jax.lax.dynamic_index_in_dim(lanes, n_lanes - 1 - j,
                                         keepdims=False)[perm]
        _, pos = jax.lax.sort((k, iota), num_keys=1, is_stable=True)
        return perm[pos]

    return jax.lax.fori_loop(0, n_lanes, one_lane, iota)


def interleaved_key_np(words: np.ndarray, bits: int = SAX_BITS) -> np.ndarray:
    """Numpy mirror of `interleaved_key` for the host-side build pipeline.

    `IndexBuilder`'s route/sort/merge phases compare keys on the host
    (numpy stable sorts are the merge primitive), so the key computation
    must not round-trip through the device per part.  Integer math only —
    bit-identical to the jnp version (asserted by tests/test_builder.py::
    test_interleaved_key_np_matches_jnp).
    Returns int32 lanes; lexicographic lane comparison == full-key
    comparison, exactly as in `interleaved_key`.
    """
    words = np.asarray(words)
    w = words.shape[-1]
    total = w * bits
    planes = np.empty(words.shape[:-1] + (total,), np.int32)
    i = 0
    for b in range(bits - 1, -1, -1):          # MSB plane first
        for s in range(w):
            planes[..., i] = (words[..., s].astype(np.int32) >> b) & 1
            i += 1
    lanes = []
    for lane_start in range(0, total, 31):
        chunk = planes[..., lane_start:lane_start + 31]
        width = chunk.shape[-1]
        weights = (np.int32(1) << np.arange(width - 1, -1, -1,
                                            dtype=np.int32))
        lanes.append(np.sum(chunk * weights, axis=-1, dtype=np.int32))
    return np.stack(lanes, axis=-1)


def lexsort_keys(keys: np.ndarray) -> np.ndarray:
    """Stable ascending order of multi-lane keys (primary lane first).

    numpy's lexsort takes its PRIMARY key last; ties break by position
    (stable), which is what makes run merging order-equivalent to one
    global stable sort.  keys: (n, n_lanes) -> (n,) permutation.
    """
    return np.lexsort(tuple(keys[:, i]
                            for i in range(keys.shape[1] - 1, -1, -1)))


def pack_keys_bytes(keys: np.ndarray) -> np.ndarray:
    """Pack (n, n_lanes) int32 key lanes into (n,) fixed-width byte
    strings whose memcmp order EQUALS the lexicographic lane order.

    Lanes are non-negative (31 bits used), so big-endian uint32 bytes
    compare like the integers, and concatenating the lanes' bytes
    compares like the lane tuple.  This gives the merge path a SCALAR
    comparable key: np.searchsorted over packed core keys is a true
    binary search, so merging a delta run into the sorted core is
    O(m log n) instead of a full O((n+m) log (n+m)) re-sort.
    """
    be = np.ascontiguousarray(keys.astype(">u4"))
    return be.view(f"S{4 * keys.shape[1]}").reshape(-1)


# ---------------------------------------------------------------------------
# Distances
# ---------------------------------------------------------------------------
def euclidean_sq(q: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Squared Euclidean distance along last axis (broadcasts)."""
    d = q - x
    return jnp.sum(d * d, axis=-1)


def euclidean(q: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    return jnp.sqrt(euclidean_sq(q, x))


def paa_lb_sq(q_paa: jnp.ndarray, x_paa: jnp.ndarray,
              series_len: int = SERIES_LEN) -> jnp.ndarray:
    """Squared PAA lower bound:  (n/w) * ||PAA(q) - PAA(x)||^2  <=  ED^2."""
    w = q_paa.shape[-1]
    return (series_len / w) * euclidean_sq(q_paa, x_paa)


def symbol_region(sym: jnp.ndarray, depth_bits: jnp.ndarray | int,
                  bits: int = SAX_BITS,
                  dtype=jnp.float32) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(lo, hi) of the N(0,1) region covered by symbol `sym` when only its top
    `depth_bits` bits are considered (an iSAX tree-node prefix).

    sym: full-cardinality symbols (uint8).  depth_bits may broadcast.
    """
    pad = jnp.asarray(padded_breakpoints(bits), dtype=dtype)  # (2^bits + 1,)
    shift = bits - jnp.asarray(depth_bits, dtype=jnp.int32)
    base = (sym.astype(jnp.int32) >> shift) << shift   # region start at depth
    lo = pad[base]
    hi = pad[base + (1 << shift)]
    return lo, hi


def mindist_region_sq(q_paa: jnp.ndarray, lo: jnp.ndarray, hi: jnp.ndarray,
                      series_len: int = SERIES_LEN) -> jnp.ndarray:
    """Squared MINDIST between a query PAA and a per-segment [lo, hi] region.

    Per segment: 0 if q in [lo, hi]; else squared distance to nearest edge.
    q_paa, lo, hi: (..., w) broadcastable.  Returns (...,).
    """
    w = q_paa.shape[-1]
    below = jnp.maximum(lo - q_paa, 0.0)
    above = jnp.maximum(q_paa - hi, 0.0)
    d = below + above  # at most one is non-zero
    return (series_len / w) * jnp.sum(d * d, axis=-1)


def mindist_isax_sq(q_paa: jnp.ndarray, words: jnp.ndarray,
                    depth_bits: jnp.ndarray | int = SAX_BITS,
                    bits: int = SAX_BITS,
                    series_len: int = SERIES_LEN) -> jnp.ndarray:
    """Squared lower-bound distance MINDIST(Q, iSAX(X)) (paper Section II).

    With depth_bits = bits this is the full-cardinality point-to-region bound;
    smaller depth emulates internal tree nodes.
    """
    lo, hi = symbol_region(words, depth_bits, bits, dtype=q_paa.dtype)
    return mindist_region_sq(q_paa, lo, hi, series_len)
