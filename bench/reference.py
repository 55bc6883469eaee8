"""The plain reference: exact k-NN under z-normalized Euclidean distance
by a brute-force scan, and the comparison that decides `correct`.

It imports nothing of the program under test and reads nothing the
program made: it scans the series as `data` makes them from the seed.
The scan selects candidates in matmul form at HIGHEST precision,
`margin` more than asked for, and ranks them again in float64 on the
host, so ties and cancellation in float32 cannot change its answer.

The series come as a list of parts, `(first_row, make)`: `make()`
returns the part's (n_part, L) rows on a device, and row j of a part is
series id `first_row + j`.  Each pass visits the parts in turn, makes
each when it visits it and frees it before the next is made, so no
device holds more than one part and one chunk's working set.  Each part
is scanned in chunks as a whole array would be, and the candidates are
merged over parts on the host: a one-part list gives the answers of one
array.

Two numbers are compared, each the widest over every answer checked:

  dist_err  |reported distance - float64 distance of the reported id|,
            relative to the latter: is each distance the distance of
            its id, as exactly as float32 holds it?
  rank_gap  |float64 distance of the j-th reported id - the reference's
            j-th distance|, relative to the latter: are the ids the
            k nearest, in order?  A missing, repeated or out-of-range
            id reads infinity.

`control_scan` is the same scan in the precision one step below the
configuration's (float32 at HIGH, three bf16 passes, for float32 at
HIGHEST), with its matmul-form distances reported as they come: the
control that a sound limit has to fail.
"""

from __future__ import annotations

import functools

import numpy as np

#: rows of the series scanned per call, and queries per call
CHUNK_ROWS = 1 << 18
QUERY_BLOCK = 512
#: extra candidates the float32 scan keeps for the float64 ranking
MARGIN = 16
#: z-normalization's epsilon, as the paper's preprocessing has it
ZNORM_EPS = 1e-8


def _znorm(x):
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    sd = jnp.std(x, axis=-1, keepdims=True)
    return (x - mu) / (sd + ZNORM_EPS)


def _dot_highest(a, b):
    import jax
    import jax.numpy as jnp
    return jnp.dot(a, b.T, precision=jax.lax.Precision.HIGHEST)


def _dot_bf16x3(a, b):
    """float32 product from three bfloat16 passes (what Precision.HIGH
    runs on a TPU), written out so that it reads the same on any
    backend."""
    import jax.numpy as jnp

    def split(v):
        hi = v.astype(jnp.bfloat16)
        lo = (v - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        return hi, lo

    (ah, al), (bh, bl) = split(a), split(b)

    def mm(u, v):
        return jnp.dot(u, v.T, preferred_element_type=jnp.float32)
    return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))


_DOTS = {"highest": _dot_highest, "bf16x3": _dot_bf16x3}


@functools.lru_cache(maxsize=None)
def _chunk_fn(rows: int, n_cand: int, dot: str):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def best(x, lo, qz, q_sq):
        xz = _znorm(jax.lax.dynamic_slice_in_dim(x, lo, rows))
        d2 = (q_sq[:, None] + jnp.sum(xz * xz, axis=-1)[None, :]
              - 2.0 * _DOTS[dot](qz, xz))
        neg, idx = jax.lax.top_k(-d2, n_cand)
        return -neg, idx + lo

    @jax.jit
    def merge(d_a, i_a, d_b, i_b):
        d = jnp.concatenate([d_a, d_b], axis=1)
        i = jnp.concatenate([i_a, i_b], axis=1)
        neg, pos = jax.lax.top_k(-d, n_cand)
        return -neg, jnp.take_along_axis(i, pos, axis=1)

    return best, merge


def _visit(parts, fn) -> list:
    """[fn(make(), first_row) for each part]: each part resident only
    while `fn` runs on it."""
    return [fn(make(), first) for first, make in parts]


def _scan_part(x, first: int, queries, n_cand: int, dot: str,
               exact: bool):
    """`scan` of one resident part: ids offset by `first`."""
    import jax.numpy as jnp
    n = x.shape[0]
    rows = min(CHUNK_ROWS, n)
    if n % rows:
        raise ValueError(f"{n} series do not split into {rows}-row chunks")
    best, merge = _chunk_fn(rows, n_cand, dot)
    out_d, out_i = [], []
    for q0 in range(0, queries.shape[0], QUERY_BLOCK):
        qz = _znorm(jnp.asarray(queries[q0:q0 + QUERY_BLOCK]))
        q_sq = jnp.sum(qz * qz, axis=-1)
        d = i = None
        for lo in range(0, n, rows):
            dc, ic = best(x, jnp.int32(lo), qz, q_sq)
            d, i = (dc, ic) if d is None else merge(d, i, dc, ic)
        out_d.append(np.asarray(d))
        out_i.append(np.asarray(i))
    d, ids = np.concatenate(out_d), np.concatenate(out_i) + first
    if not exact:
        return d, ids
    return d, ids, _distances_rows(x, first, queries, ids,
                                   np.full(ids.shape, np.nan))


def _merge_parts(found: list, n_cand: int):
    """The n_cand smallest first columns, per query, of the parts'
    (d, ids, ...) tuples, with their companions; one part as it is."""
    if len(found) == 1:
        return found[0]
    cols = [np.concatenate(c, axis=1) for c in zip(*found)]
    order = np.argsort(cols[0], axis=1, kind="stable")[:, :n_cand]
    return tuple(np.take_along_axis(c, order, axis=1) for c in cols)


def scan(parts, queries, n_cand: int, dot: str = "highest",
         exact: bool = False):
    """Squared distances and ids, (Q, n_cand) each on the host, of the
    n_cand nearest rows of the parts to each query, in matmul form; with
    `exact`, also their float64 distances, read while each part is
    resident."""
    return _merge_parts(_visit(parts, lambda x, first: _scan_part(
        x, first, queries, n_cand, dot, exact)), n_cand)


def _znorm64(a: np.ndarray) -> np.ndarray:
    a = a.astype(np.float64)
    a = a - a.mean(-1, keepdims=True)
    return a / (a.std(-1, keepdims=True) + ZNORM_EPS)


def _distances_rows(x, first: int, queries: np.ndarray, ids: np.ndarray,
                    out: np.ndarray) -> np.ndarray:
    """Fill `out` where `ids` lie in the resident part `x`."""
    import jax.numpy as jnp
    mine = (ids >= first) & (ids < first + x.shape[0])
    if mine.any():
        rows = _znorm64(np.asarray(jnp.take(
            x, jnp.asarray(ids[mine] - first), axis=0)))
        q = _znorm64(np.asarray(queries))[np.nonzero(mine)[0]]
        out[mine] = np.sqrt(((rows - q) ** 2).sum(-1))
    return out


def distances64(parts, queries: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """float64 distances of the series `ids` ((Q, c)) to their queries,
    each row taken from its own part; NaN for an id no part holds."""
    out = np.full(ids.shape, np.nan)
    _visit(parts, lambda x, first: _distances_rows(x, first, queries, ids,
                                                   out))
    return out


class Reference:
    """Exact k nearest (float64 distances and ids) of each query, over
    the series in `parts`."""

    def __init__(self, parts, queries: np.ndarray, k: int):
        d2, cand, d64 = scan(parts, queries, k + MARGIN, exact=True)
        order = np.argsort(d64, axis=1, kind="stable")[:, :k]
        self.k = k
        self.dist = np.take_along_axis(d64, order, axis=1)
        self.ids = np.take_along_axis(cand, order, axis=1)
        # the float32 scan is certain of its k nearest only where the
        # last candidate it kept lies clearly beyond the k-th
        self.unsure = int((np.sqrt(np.maximum(d2[:, -1], 0.0))
                           <= self.dist[:, -1] * (1 + 1e-4)).sum())


def control_scan(parts, queries, k: int):
    """The reference in the control's precision, reported as it comes:
    (Q, k) distances and ids."""
    d2, ids = scan(parts, queries, k, dot="bf16x3")
    return np.sqrt(np.maximum(d2, 0.0)), ids


def compare(parts, queries: np.ndarray, ref: Reference, dist: np.ndarray,
            ids: np.ndarray):
    """Per query: (dist_err, rank_gap) of the answer (dist, ids) against
    `ref`, as the module docstring defines them."""
    Q, k = ref.dist.shape
    dist = np.asarray(dist, np.float64).reshape(Q, -1)
    ids = np.asarray(ids).reshape(Q, -1).astype(np.int64)
    if ids.shape[1] != k or dist.shape[1] != k:
        return np.full(Q, np.inf), np.full(Q, np.inf)
    d64 = distances64(parts, queries, ids)
    bad = np.isnan(d64).any(1)                  # an id that no part holds
    srt = np.sort(ids, axis=1)
    bad |= (srt[:, 1:] == srt[:, :-1]).any(1)
    bad |= ~np.isfinite(dist).all(1)
    tiny = np.finfo(np.float32).tiny
    dist_err = (np.abs(dist - d64) / np.maximum(d64, tiny)).max(1)
    rank_gap = (np.abs(d64 - ref.dist)
                / np.maximum(ref.dist, tiny)).max(1)
    dist_err[bad] = np.inf
    rank_gap[bad] = np.inf
    return dist_err, rank_gap
