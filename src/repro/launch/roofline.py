"""Roofline-term extraction from a compiled (SPMD-partitioned) module.

XLA's `compiled.cost_analysis()` counts while-loop bodies exactly ONCE
(verified empirically: a 10-step scan of a matmul reports the flops of one
matmul), so for scan-over-layers models it under-counts by ~n_layers.  We
therefore do our own accounting directly on the post-optimization HLO text:

  * the executed-computation set is walked from ENTRY through while ops,
    with each body/condition weighted by the loop's `known_trip_count`
    (emitted by XLA in backend_config — exact for lax.scan);
  * FLOPs  = 2 * numel(result) * prod(contracting dims) summed over `dot`
    ops (matmuls are >95% of model FLOPs; elementwise is not counted —
    stated in EXPERIMENTS.md);
  * HBM bytes = operand + result bytes of every materializing op (fusions
    count their boundary, internals live in registers; bitcast/tuple/GTE/
    parameter are free) — the standard roofline traffic upper bound;
  * collective bytes = result-shape bytes of all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute.

All numbers are PER DEVICE (the SPMD module is the per-device program);
terms divide by per-chip peak rates:

    compute    = flops / 197e12          (bf16 MXU peak)
    memory     = bytes / 819e9           (HBM)
    collective = coll_bytes / 50e9       (ICI, 1 link counted)
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from .mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_FREE_OPS = {"bitcast", "tuple", "get-tuple-element", "parameter",
             "constant", "after-all", "add-dependency", "while",
             "conditional", "call", "partition-id", "replica-id", "domain",
             "opt-barrier"}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# tuple result types embed `/*index=N*/` comments (which contain '='), so
# the tuple branch must match any non-paren content, not just non-'='.
_OP_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*"
                    r"((?:\([^()]*\))|(?:\w+\[[\d,]*\](?:\{[\d,]*\})?))\s+"
                    r"([\w\-]+)")
_WHILE_RE = re.compile(
    r"condition=%?([\w\.\-]+),\s*body=%?([\w\.\-]+)")
_TRIP_RE = re.compile(r'known_trip_count[\\"{:n\s]+(\d+)')
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")


def _shape_elems_bytes(type_str: str) -> Tuple[int, int]:
    elems_total, bytes_total = 0, 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        elems_total += n
        bytes_total += n * _DTYPE_BYTES[dt]
    return elems_total, bytes_total


def _shape_bytes(type_str: str) -> int:
    return _shape_elems_bytes(type_str)[1]


def _split_computations(hlo: str) -> Tuple[Dict[str, List[str]], str]:
    """name -> list of body lines; also returns the ENTRY computation name."""
    comps: Dict[str, List[str]] = {}
    entry = None
    name = None
    for line in hlo.splitlines():
        if name is None:
            m = re.match(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->.*\{", line)
            if m:
                name = m.group(2)
                comps[name] = []
                if m.group(1):
                    entry = name
        else:
            if line.startswith("}"):
                name = None
            else:
                comps[name].append(line)
    return comps, entry


def _dot_flops(line: str, shapes: Dict[str, str], result_type: str) -> float:
    """FLOPs of a dot op: 2 * numel(result) * prod(lhs contracting dims)."""
    res_elems, _ = _shape_elems_bytes(result_type)
    m = re.search(r"dot[\.\d]*\(([^)]*)\)", line)
    if not m:
        return 0.0
    ops = _OPERAND_RE.findall(m.group(1))
    if not ops:
        return 0.0
    lhs_type = shapes.get(ops[0], "")
    sm = _SHAPE_RE.search(lhs_type)
    if not sm:
        return 0.0
    dims = [int(d) for d in sm.group(2).split(",")] if sm.group(2) else []
    cm = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", line)
    contract = 1
    if cm and cm.group(1):
        for ci in cm.group(1).split(","):
            contract *= dims[int(ci)]
    return 2.0 * res_elems * contract


@dataclass
class HLOCost:
    flops: float = 0.0
    bytes_hbm: float = 0.0
    bytes_coll: float = 0.0
    coll_by_kind: Dict[str, int] = field(default_factory=dict)
    coll_count: int = 0
    by_computation: Dict[str, dict] = field(default_factory=dict)


def analyze_hlo(hlo: str) -> HLOCost:
    comps, entry = _split_computations(hlo)
    # global op-name -> result type (names are unique module-wide)
    shapes: Dict[str, str] = {}
    for lines in comps.values():
        for line in lines:
            m = _OP_RE.match(line)
            if m:
                shapes[m.group(1)] = m.group(2)

    # executed-computation multipliers: ENTRY + while bodies/conds
    mult: Dict[str, int] = {}

    def visit(name: str, m: int):
        if name not in comps or m <= 0:
            return
        if name in mult and mult[name] >= m:
            return
        mult[name] = max(mult.get(name, 0), m)
        for line in comps[name]:
            if " while(" in line:
                w = _WHILE_RE.search(line)
                if not w:
                    continue
                t = _TRIP_RE.search(line)
                trip = int(t.group(1)) if t else 1
                visit(w.group(2), m * trip)
                visit(w.group(1), m * (trip + 1))

    if entry:
        visit(entry, 1)
    else:
        for n in comps:
            mult[n] = 1

    cost = HLOCost()
    for name, m in mult.items():
        c_flops = c_bytes = c_coll = 0.0
        for line in comps[name]:
            om = _OP_RE.match(line)
            if not om:
                continue
            opname, rtype, okind = om.groups()
            if okind in _FREE_OPS:
                continue
            rbytes = _shape_bytes(rtype)
            if okind in ("dynamic-slice", "gather", "slice", "broadcast",
                         "iota", "reduce-window"):
                # reads only a result-sized window of the operand
                c_bytes += 2 * rbytes
            elif okind == "fusion" and ("dynamic-slice" in opname
                                        or "dynamic_slice" in opname):
                c_bytes += 2 * rbytes
            elif okind in ("dynamic-update-slice", "scatter") or (
                    okind == "fusion" and ("dynamic-update-slice" in opname
                                           or "dynamic_update_slice" in opname)):
                # in-place update: the result aliases the big operand; real
                # traffic is the update-sized region.  Charge the non-result-
                # shaped operands (the update + small indices) twice.
                pm = re.search(okind + r"[\.\d]*\(([^)]*)\)", line)
                ub = 0
                if pm:
                    for op in _OPERAND_RE.findall(pm.group(1)):
                        ot = shapes.get(op, "")
                        if ot and _SHAPE_RE.search(ot) and \
                                ot.split("{")[0] != rtype.split("{")[0]:
                            ub += _shape_bytes(ot)
                c_bytes += 2 * (ub or rbytes // max(1, 64))
            else:
                # operand bytes resolved through the global shape map
                obytes = 0
                pm = re.search(okind + r"[\.\d]*\(([^)]*)\)", line)
                if pm:
                    for op in _OPERAND_RE.findall(pm.group(1)):
                        obytes += _shape_bytes(shapes.get(op, ""))
                c_bytes += rbytes + obytes
            if okind == "dot":
                c_flops += _dot_flops(line, shapes, rtype)
            if okind in _COLLECTIVES:
                c_coll += rbytes
                cost.coll_by_kind[okind] = \
                    cost.coll_by_kind.get(okind, 0) + rbytes * m
                cost.coll_count += m
        if c_flops or c_bytes:
            cost.by_computation[name] = {
                "mult": m, "flops": c_flops * m, "bytes": c_bytes * m,
                "coll": c_coll * m}
        cost.flops += c_flops * m
        cost.bytes_hbm += c_bytes * m
        cost.bytes_coll += c_coll * m
    return cost


@dataclass
class Roofline:
    flops: float                  # per device
    bytes_hbm: float              # per device
    bytes_coll: float             # per device
    t_compute: float
    t_memory: float
    t_collective: float
    dominant: str
    model_flops: Optional[float] = None
    useful_ratio: Optional[float] = None
    coll_by_kind: Dict[str, int] = field(default_factory=dict)
    xla_flops: Optional[float] = None      # raw cost_analysis (loops x1)
    xla_bytes: Optional[float] = None

    def as_dict(self) -> dict:
        return {k: getattr(self, k) for k in
                ("flops", "bytes_hbm", "bytes_coll", "t_compute", "t_memory",
                 "t_collective", "dominant", "model_flops", "useful_ratio",
                 "coll_by_kind", "xla_flops", "xla_bytes")}


def analyze(compiled, n_chips: int,
            model_flops_global: Optional[float] = None,
            hlo: Optional[str] = None) -> Roofline:
    xla_cost = compiled.cost_analysis()
    if isinstance(xla_cost, list):
        xla_cost = xla_cost[0]
    hc = analyze_hlo(hlo if hlo is not None else compiled.as_text())

    t_c = hc.flops / PEAK_FLOPS_BF16
    t_m = hc.bytes_hbm / HBM_BW
    t_x = hc.bytes_coll / ICI_BW
    dom = max((("compute", t_c), ("memory", t_m), ("collective", t_x)),
              key=lambda kv: kv[1])[0]
    mf = model_flops_global / n_chips if model_flops_global else None
    ratio = (mf / hc.flops) if (mf and hc.flops) else None
    return Roofline(flops=hc.flops, bytes_hbm=hc.bytes_hbm,
                    bytes_coll=hc.bytes_coll,
                    t_compute=t_c, t_memory=t_m, t_collective=t_x,
                    dominant=dom, model_flops=mf, useful_ratio=ratio,
                    coll_by_kind=hc.coll_by_kind,
                    xla_flops=float(xla_cost.get("flops", 0.0)),
                    xla_bytes=float(xla_cost.get("bytes accessed", 0.0)))


def model_flops_for(cfg, shape) -> float:
    """6*N_active*D tokens rule (train) / 2*N_active*D (fwd-only)."""
    counts = cfg.param_counts()
    n_active = counts["active"]
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mult = 6 if shape.kind == "train" else 2
    return mult * n_active * tokens


# ---------------------------------------------------------------------- #
# device peaks and the refine round's analytic cost
# ---------------------------------------------------------------------- #
#: (peak_flops, hbm_bytes_per_s) per `jax.devices()[0].device_kind`,
#: matched exactly.  A kind missing here is an error, never a default.
#:   "TPU v5 lite" (TPU v5e): 197 TFLOP/s bf16, 819 GB/s HBM — Google
#:       Cloud documentation, "TPU v5e".
DEVICE_PEAKS = {
    "TPU v5 lite": (197e12, 819e9),
}


def device_peaks(kind: Optional[str] = None) -> Tuple[float, float]:
    """(peak_flops, hbm_bytes_per_s) for device kind `kind` (None = the
    live device's `device_kind`).  Raises KeyError for a kind that is
    not in `DEVICE_PEAKS`: a roofline share against a guessed peak is a
    wrong number."""
    if kind is None:
        import jax
        kind = str(jax.devices()[0].device_kind)
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(DEVICE_PEAKS)}") from None


def refine_analytic(Q: int, K: int, M: int, L: int, k: int,
                    dtype_bytes: int = 4) -> Dict[str, float]:
    """Analytic cost of ONE refine round: flops + HBM bytes for the
    fused kernel (each (M, L) leaf block streamed exactly once) and the
    materializing ref path (gather written out + read back + source).
    The single source of truth behind `benchmarks.roofline_table.
    refine_rows`."""
    flops = 2.0 * Q * K * M * L
    leaf = float(dtype_bytes) * Q * K * M * L     # gathered member rows
    small = 4.0 * Q * L + 12.0 * Q * k            # queries + BSF buffers
    return {"flops": flops,
            "bytes_fused": leaf + small,
            "bytes_mat": 3.0 * leaf + small}

