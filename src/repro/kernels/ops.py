"""Public jit'd entry points for the Pallas kernels.

On CPU the kernels execute in interpret mode (kernel body run in Python —
bit-identical semantics, no Mosaic); on TPU they compile to Mosaic.
`_compat.resolve_interpret` picks the default from the platform at call
time; every op also takes an explicit override for tests.  The raw kernel
modules default `interpret=None` and resolve through it too, so a direct
caller gets Mosaic on TPU instead of silently running the Python
interpreter.
"""

from __future__ import annotations

from ._compat import resolve_interpret
from .ed_argmin import ed_argmin as _ed_argmin
from .isax_summarize import summarize as _summarize
from .lb_distance import lb_distance as _lb_distance
from .refine import refine_topk as _refine_topk


def summarize(x, *, segments=None, bits=None, znorm=True, interpret=None):
    from repro.core import isax
    return _summarize(
        x,
        segments=isax.SEGMENTS if segments is None else segments,
        bits=isax.SAX_BITS if bits is None else bits,
        znorm=znorm,
        interpret=resolve_interpret(interpret))


def lb_distance(q_paa, leaf_lo, leaf_hi, *, series_len=None, interpret=None):
    from repro.core import isax
    return _lb_distance(
        q_paa, leaf_lo, leaf_hi,
        series_len=isax.SERIES_LEN if series_len is None else series_len,
        interpret=resolve_interpret(interpret))


def ed_argmin(q, xs, *, interpret=None):
    return _ed_argmin(q, xs, interpret=resolve_interpret(interpret))


def refine_topk(q, q_sq, series, sq_norms, leaf_ids, alive, bsf_d, bsf_e,
                *, leaf_capacity, k, interpret=None, dma_depth=1,
                lowering=None):
    # interpret is passed through RAW (not pre-resolved):
    # _compat.resolve_lowering must see `None` to pick interpret per
    # platform — TPU compiles Mosaic, CPU interprets, anything else
    # raises the typed KernelLoweringError at dispatch time.
    return _refine_topk(q, q_sq, series, sq_norms, leaf_ids, alive,
                        bsf_d, bsf_e, leaf_capacity=leaf_capacity, k=k,
                        interpret=interpret, dma_depth=dma_depth,
                        lowering=lowering)


def flash_attention(q, k, v, *, causal=True, window=0, block_q=128,
                    interpret=None):
    from .flash_attention import flash_attention as _fa
    return _fa(q, k, v, causal=causal, window=window, block_q=block_q,
               interpret=resolve_interpret(interpret))
