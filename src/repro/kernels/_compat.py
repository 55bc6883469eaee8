"""Shared kernel-module plumbing (a leaf module — no package imports, so
every kernel module can use it without cycling through ops.py).

Interpret mode (kernel body run in Python — bit-identical semantics, no
Mosaic) is the CPU's way to execute a Pallas kernel; on TPU kernels
compile to Mosaic.  The choice is made when a kernel is called, from the
platform JAX reports then, not when this module is imported.

`resolve_lowering` is the one dispatch point for `backend="pallas"`: it
raises the typed `KernelLoweringError` — instead of an opaque Mosaic
trace-time failure — on a platform with no lowering path at all.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax

#: platform string (jax.default_backend() spelling) -> the compiled
#: lowering path kernels take there.  CPU is deliberately absent: it has
#: NO compiled path — interpret mode is the only way to execute a Pallas
#: kernel there, and `resolve_lowering` falls back to it rather than
#: erroring.
LOWERINGS = {"tpu": "mosaic"}


class KernelLoweringError(RuntimeError):
    """`backend="pallas"` was requested on a platform with no kernel
    lowering path (and interpret mode was explicitly disabled).  Raised
    at dispatch time with the platform and the supported set, so callers
    see a clear capability error instead of a Mosaic trace-time stack."""


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """None -> interpret everywhere except TPU (where Mosaic compiles).

    Raw kernels default interpret=None and resolve through this, so a
    direct caller never silently runs the Python interpreter on TPU.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def resolve_lowering(interpret: Optional[bool] = None,
                     lowering: Optional[str] = None,
                     platform: Optional[str] = None
                     ) -> Tuple[str, bool]:
    """Resolve a kernel's `(lowering, interpret)` for a platform.

    `lowering` is 'mosaic' or None (the Mosaic structures are the only
    ones; anything else is a ValueError); `interpret` whether the kernel
    compiles or runs in the Python interpreter.  Defaults (both None):
    TPU compiles Mosaic, CPU interprets, and any OTHER platform raises
    `KernelLoweringError` — a platform like 'gpu' must fail HERE, not
    five frames deep in a lowering trace.

    `platform` overrides `jax.default_backend()` (tests exercise the
    per-platform matrix without owning the hardware).
    """
    if lowering not in (None, "mosaic"):
        raise ValueError(
            f"lowering must be 'mosaic' or None, got {lowering!r}")
    p = jax.default_backend() if platform is None else platform
    compiled = LOWERINGS.get(p)
    if interpret is None:
        # only CPU falls back to interpret mode by default; an unknown
        # platform must fail the typed way below unless the caller opts
        # into the interpreter explicitly
        interpret = compiled is None and p == "cpu"
    if not interpret and compiled is None:
        raise KernelLoweringError(
            f"backend='pallas' has no kernel lowering path on "
            f"platform {p!r} (supported: {sorted(LOWERINGS)} compile, "
            f"'cpu' interprets); pass backend='ref' or interpret=True")
    return "mosaic", bool(interpret)
