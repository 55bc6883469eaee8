"""Pallas TPU kernel: fused causal (flash-style) attention with GQA.

Motivation (EXPERIMENTS.md §Perf, granite-8b train_4k): the unfused HLO
attention round-trips the (B, H, Tq, S) score/softmax tensors through HBM
— at T=4096 that is ~67 MB f32 per (batch, head) per direction, the
single largest term of the cell's memory roofline.  Fusing QK^T -> mask ->
softmax -> @V keeps scores in VMEM: HBM traffic drops to the roofline
floor (read Q,K,V + write O).

Tiling: grid (B * Hq, Tq / BQ).  Each program holds one (BQ, dh) query
tile plus this (b, kv-head)'s FULL (S, dh) K and V tiles in VMEM — at
S=4096, dh=128, bf16 that is 2 MB each, comfortable in ~16 MB v5e VMEM
(double-buffered).  For S beyond ~8k, K/V would be streamed in blocks with
an online-softmax carry; this variant targets the train_4k hot spot and
asserts its envelope.  dims are MXU-aligned (BQ, dh multiples of 128 when
the inputs are).

GQA: query head h reads kv head h // (Hq // Hkv) via the K/V index_map —
no KV replication in memory.

Validated in interpret mode against ref.flash_attention_ref (tests sweep
shapes/dtypes); used on TPU via ops.flash_attention.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float, bq: int,
                  causal: bool, window: int):
    qi = pl.program_id(1)                     # query block index
    q = q_ref[0].astype(jnp.float32)          # (BQ, dh)
    k = k_ref[0].astype(jnp.float32)          # (S, dh)
    v = v_ref[0]                              # (S, dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= qpos >= kpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=1, keepdims=True)
    o = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[0] = (o / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, interpret: bool = None):
    """q: (B, Hq, T, dh); k/v: (B, Hkv, S, dh) -> (B, Hq, T, dh).

    interpret=None resolves via _compat.resolve_interpret (Mosaic on TPU).
    """
    from ._compat import resolve_interpret
    interpret = resolve_interpret(interpret)
    B, Hq, T, dh = q.shape
    _, Hkv, S, _ = k.shape
    assert Hq % Hkv == 0
    G = Hq // Hkv
    bq = min(block_q, T)
    assert T % bq == 0, (T, bq)
    scale = dh ** -0.5

    qf = q.reshape(B * Hq, T, dh)
    kf = k.reshape(B * Hkv, S, dh)
    vf = v.reshape(B * Hkv, S, dh)

    grid = (B * Hq, T // bq)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, bq=bq, causal=causal,
                          window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, S, dh), lambda i, j, G=G: (i // G, 0, 0)),
            pl.BlockSpec((1, S, dh), lambda i, j, G=G: (i // G, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, T, dh), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, Hq, T, dh)
