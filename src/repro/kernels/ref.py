"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each function mirrors the semantics of one kernel in this package exactly;
tests sweep shapes/dtypes and assert_allclose kernel-vs-oracle.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import isax


def summarize_ref(x: jnp.ndarray, segments: int = isax.SEGMENTS,
                  bits: int = isax.SAX_BITS,
                  znorm: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(z-norm) -> PAA -> iSAX words.  x: (n, L) -> (n, w) f32, (n, w) i32."""
    if znorm:
        x = isax.znormalize(x)
    p = isax.paa(x.astype(jnp.float32), segments)
    w = isax.sax_word(p, bits).astype(jnp.int32)
    return p, w


def lb_distance_ref(q_paa: jnp.ndarray, leaf_lo: jnp.ndarray,
                    leaf_hi: jnp.ndarray,
                    series_len: int = isax.SERIES_LEN) -> jnp.ndarray:
    """Squared MINDIST of every query PAA against every leaf region.

    q_paa: (Q, w); leaf_lo/hi: (NL, w) -> (Q, NL) f32.
    """
    return isax.mindist_region_sq(q_paa[:, None, :], leaf_lo[None],
                                  leaf_hi[None], series_len)


def ed_argmin_ref(q: jnp.ndarray, xs: jnp.ndarray
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-query min squared Euclidean distance + argmin over candidates.

    q: (Q, L); xs: (N, L) -> (Q,) f32 min-dist^2, (Q,) i32 argmin.
    """
    q = q.astype(jnp.float32)
    xs = xs.astype(jnp.float32)
    d2 = (jnp.sum(q * q, -1)[:, None] + jnp.sum(xs * xs, -1)[None, :]
          - 2.0 * jnp.dot(q, xs.T, precision=jax.lax.Precision.HIGHEST))
    d2 = jnp.maximum(d2, 0.0)
    i = jnp.argmin(d2, axis=1).astype(jnp.int32)
    return jnp.take_along_axis(d2, i[:, None].astype(jnp.int32), 1)[:, 0], i


def refine_topk_ref(q: jnp.ndarray, q_sq: jnp.ndarray, series: jnp.ndarray,
                    sq_norms: jnp.ndarray, leaf_ids: jnp.ndarray,
                    alive: jnp.ndarray, bsf_d: jnp.ndarray,
                    bsf_e: jnp.ndarray, *, leaf_capacity: int, k: int
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One refinement round, reference semantics (materializing path).

    Gathers the (Q, K*M, L) member rows, computes matmul-form squared
    distances, masks pruned leaves to BIG and folds the candidates into
    the carried (Q, k) buffer with jax.lax.top_k (ascending, ties to the
    lower union index).  This IS the allocation-heavy backend='ref' round
    that core.search dispatches to — and the oracle the fused kernel is
    tested against (identical entry buffers; distances to the last ulp).
    """
    big = jnp.float32(1e30)
    Q, L = q.shape
    M = leaf_capacity
    entry = leaf_ids[..., None] * M + jnp.arange(M)[None, None, :]
    entry = entry.reshape(Q, -1).astype(jnp.int32)          # (Q, K*M)
    xs = jnp.take(series, entry, axis=0).astype(jnp.float32)
    xn = jnp.take(sq_norms, entry, axis=0).astype(jnp.float32)
    dots = jnp.einsum("qnl,ql->qn", xs, q.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
    d2 = jnp.maximum(q_sq[:, None] + xn - 2.0 * dots, 0.0)
    d2 = jnp.where(jnp.repeat(alive.astype(bool), M, axis=1), d2, big)
    alld = jnp.concatenate([bsf_d, d2], axis=1)
    alle = jnp.concatenate([bsf_e, entry], axis=1)
    neg, pos = jax.lax.top_k(-alld, k)
    return -neg, jnp.take_along_axis(alle, pos, axis=1)


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = True, window: int = 0) -> jnp.ndarray:
    """Plain softmax attention oracle.  q: (B,Hq,T,dh); k/v: (B,Hkv,S,dh)."""
    B, Hq, T, dh = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qf = q.reshape(B, Hkv, G, T, dh).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    s = jnp.einsum("bkgtd,bksd->bkgts", qf, kf) * (dh ** -0.5)
    qpos = jnp.arange(T)[:, None]
    kpos = jnp.arange(S)[None, :]
    mask = jnp.ones((T, S), bool)
    if causal:
        mask &= qpos >= kpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgts,bksd->bkgtd", w, v.astype(jnp.float32))
    return o.reshape(B, Hq, T, dh).astype(q.dtype)
