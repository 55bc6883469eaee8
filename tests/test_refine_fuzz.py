"""Hypothesis fuzz sweep over the fused refine kernel's exactness
contract, across every (Q, K, M, L, dtype, kernel structure) the
dispatcher can take (skips cleanly when hypothesis is absent).

Two layers, two contracts (see kernels/refine.py's module docstring):

* kernel level — every structure (the pipelined dma_depth=1 kernel and
  the dma_depth>=2 DMA ring at two depths) returns the SAME entry
  buffer bit for bit as the materializing oracle `ref.refine_topk_ref`,
  with distances within a few ULP (XLA may re-associate the oracle's
  batched einsum; the kernels accumulate in a fixed order — empirical
  worst over 10^3 sweeps is 3 ULP, gated at 8 for slack: a real defect
  diverges by orders of magnitude, not units-in-the-last-place);
* run_search level — the full search is bitwise identical between
  backend='ref' and backend='pallas' (winners' distances are recomputed
  in direct form from identical entry buffers), and id-identical to the
  brute-force oracle.

Degenerate shapes ride inside the strategies: all-pruned rounds
(alive_mode='none'), a single leaf (NL=1), Q=1, and k larger than the
round's candidate count (k=11 vs K*M as small as 4).
"""

import numpy as np
import pytest

pytest.importorskip("hypothesis")

import jax.numpy as jnp                                    # noqa: E402
from hypothesis import HealthCheck, given, settings        # noqa: E402
from hypothesis import strategies as st                    # noqa: E402

from repro.core import build_index, run_search, search_bruteforce  # noqa: E402
from repro.data.synthetic import random_walk               # noqa: E402
from repro.kernels import ops, ref                         # noqa: E402

# dma_depth: the pipelined kernel (1) and the DMA ring at two depths —
# every structure the autotune sweep can propose
STRUCTURES = (1, 2, 4)

# sampled (not drawn free-form) so jit caches are shared across examples
# and the 50+ cases stay fast in interpret mode.  Each example draws ONE
# structure: every distinct (shape, structure) combination is a fresh
# XLA compile whose executable holds ~65 memory mappings for the life of
# the process, and an unbounded cross-product walks the pytest process
# into the vm.max_map_count ceiling (mmap ENOMEM) long before it runs
# out of RAM.
S_Q = st.sampled_from((1, 2, 5))
S_K = st.sampled_from((1, 3, 4))
S_M = st.sampled_from((4, 8))
S_L = st.sampled_from((32, 64))
S_NL = st.sampled_from((1, 3, 9))
S_K_NN = st.sampled_from((1, 3, 11))
S_DTYPE = st.sampled_from(("float32", "bfloat16"))
S_ALIVE = st.sampled_from(("random", "none", "all"))
S_STRUCTURE = st.sampled_from(STRUCTURES)


def _ulp_diff(a, b) -> np.ndarray:
    """ULP distance between non-negative f32 arrays (distances)."""
    ai = np.ascontiguousarray(np.asarray(a, np.float32)).view(np.int32)
    bi = np.ascontiguousarray(np.asarray(b, np.float32)).view(np.int32)
    return np.abs(ai.astype(np.int64) - bi.astype(np.int64))


def _case(Q, K, M, NL, L, k, dtype, alive_mode, seed):
    rng = np.random.default_rng(seed)
    stored = jnp.asarray(rng.standard_normal((NL * M, L)),
                         getattr(jnp, dtype))
    series_f32 = stored.astype(jnp.float32)
    sqn = jnp.sum(series_f32 * series_f32, -1)
    q = jnp.asarray(rng.standard_normal((Q, L)), jnp.float32)
    qsq = jnp.sum(q * q, -1)
    ids = jnp.asarray(rng.integers(0, NL, (Q, K)), jnp.int32)
    if alive_mode == "none":
        alive = jnp.zeros((Q, K), bool)
    elif alive_mode == "all":
        alive = jnp.ones((Q, K), bool)
    else:
        alive = jnp.asarray(rng.integers(0, 2, (Q, K)).astype(bool))
    bsf_d = jnp.full((Q, k), 1e30, jnp.float32)
    bsf_e = jnp.zeros((Q, k), jnp.int32)
    return q, qsq, stored, series_f32, sqn, ids, alive, bsf_d, bsf_e


@settings(max_examples=60, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(Q=S_Q, K=S_K, M=S_M, NL=S_NL, L=S_L, k=S_K_NN, dtype=S_DTYPE,
       alive_mode=S_ALIVE, structure=S_STRUCTURE,
       seed=st.integers(0, 2**16 - 1))
def test_every_structure_matches_the_oracle(Q, K, M, NL, L, k, dtype,
                                            alive_mode, structure, seed):
    q, qsq, stored, series_f32, sqn, ids, alive, bsf_d, bsf_e = _case(
        Q, K, M, NL, L, k, dtype, alive_mode, seed)
    # the oracle sees the same stored-dtype values the kernels gather
    dr, er = ref.refine_topk_ref(q, qsq, stored, sqn, ids, alive,
                                 bsf_d, bsf_e, leaf_capacity=M, k=k)
    dr, er = np.asarray(dr), np.asarray(er)
    dd = structure
    dk, ek = ops.refine_topk(q, qsq, stored, sqn, ids, alive,
                             bsf_d, bsf_e, leaf_capacity=M, k=k,
                             interpret=True, dma_depth=dd)
    np.testing.assert_array_equal(np.asarray(ek), er, err_msg=str(
        ("entry buffer mismatch", dd,
         Q, K, M, NL, L, k, dtype, alive_mode, seed)))
    ulp = _ulp_diff(dk, dr)
    assert ulp.max(initial=0) <= 8, (
        "distance beyond 8 ULP of the oracle", dd,
        int(ulp.max()), Q, K, M, NL, L, k, dtype, alive_mode, seed)


@settings(max_examples=25, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(Q=st.sampled_from((1, 5)), k=S_K_NN, alive_mode=S_ALIVE,
       seed=st.integers(0, 2**16 - 1))
def test_structures_agree_on_the_carried_buffer(Q, k, alive_mode, seed):
    """Two chained rounds (the second folds into a non-trivial carry):
    every structure must thread the SAME buffer through both.  Shape
    axes beyond (Q, k) are pinned — this test DOES loop all three
    structures per example, so its jit-key budget is kept small."""
    K, NL, M, L = 3, 6, 8, 32
    q, qsq, stored, _, sqn, ids, alive, bsf_d, bsf_e = _case(
        Q, K, M, NL, L, k, "float32", alive_mode, seed)
    ids2 = jnp.asarray(
        np.random.default_rng(seed + 1).integers(0, NL, (Q, K)), jnp.int32)
    outs = []
    for dd in STRUCTURES:
        d1, e1 = ops.refine_topk(q, qsq, stored, sqn, ids, alive,
                                 bsf_d, bsf_e, leaf_capacity=M, k=k,
                                 interpret=True, dma_depth=dd)
        d2, e2 = ops.refine_topk(q, qsq, stored, sqn, ids2,
                                 jnp.ones_like(alive), d1, e1,
                                 leaf_capacity=M, k=k, interpret=True,
                                 dma_depth=dd)
        outs.append((dd, np.asarray(d2), np.asarray(e2)))
    _, d0, e0 = outs[0]
    for dd, d, e in outs[1:]:
        np.testing.assert_array_equal(e, e0, err_msg=str(
            ("chained entries diverged", dd, seed)))
        assert _ulp_diff(d, d0).max(initial=0) <= 8, (dd, seed)


@settings(max_examples=12, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(shape=st.sampled_from(((64, 32, 8), (130, 64, 16), (257, 64, 32))),
       k=st.sampled_from((1, 5, 10)),
       round_leaves=st.sampled_from((2, 8)),
       seed=st.integers(0, 2**12 - 1))
def test_run_search_backends_bitwise_and_oracle_ids(shape, k, round_leaves,
                                                    seed):
    n, L, cap = shape
    walks = random_walk(n, L, seed=seed % 97)
    idx = build_index(jnp.asarray(walks), leaf_capacity=cap)
    rng = np.random.default_rng(seed)
    base = walks[rng.integers(0, n, 3)]
    q = jnp.asarray(base + 0.05 * rng.standard_normal(base.shape),
                    jnp.float32)
    dr, ir = run_search(idx, q, k=k, round_leaves=round_leaves,
                        backend="ref")
    dp, ip = run_search(idx, q, k=k, round_leaves=round_leaves,
                        backend="pallas")
    np.testing.assert_array_equal(np.asarray(ip), np.asarray(ir))
    assert np.asarray(dp).tobytes() == np.asarray(dr).tobytes(), (
        "run_search distances not bitwise across backends",
        shape, k, round_leaves, seed)
    db, ib = search_bruteforce(jnp.asarray(walks), q, k=k)
    np.testing.assert_array_equal(np.asarray(ip), np.asarray(ib))
    np.testing.assert_allclose(np.asarray(dp), np.asarray(db),
                               rtol=1e-4, atol=1e-4)
