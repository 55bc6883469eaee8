"""From a profiler trace to the numbers the per-layer metrics read.

`read_xplane` takes the `.xplane.pb` that `jax.profiler` writes and keeps
three things: the device operations (one list per chip), the host
events, and the measured window, which the harness marks with a host
span named `WINDOW_SPAN`.  Everything else here works on that reduced
form, which `dump`/`load` also keep as JSON, so a recorded trace can be
reduced again without a chip.

  busy_s       union of the intervals in which an operation ran on a
               chip, inside the window, averaged over the chips
  idle_gaps    the holes in one chip's union (the lowest chip id), each
               named by what the host was doing then
  op_seconds   device time of the operations whose name matches a
               pattern, summed over the chips (how a kernel's time is
               read)
  breakdown    the ten operations that took most time, summed over the
               chips, and the lowest chip's idle time summed by what the
               host was doing

A run on several chips holds one list of operations a chip: busy time
and the idle share are a mean chip's, kernel time is all the chips'
together.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Dict, List, Optional, Tuple

#: the host span that marks the measured window
WINDOW_SPAN = "bench.window"

#: planes of the chips' operations, and the line that holds the ops
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
#: host planes whose events may name an idle gap
HOST_PLANE = "/host:CPU"

Event = Tuple[str, int, int]          # name, start ns, duration ns


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Event]]        # chip id -> device operations
    host: List[Event]                  # host events, every thread
    window: Tuple[int, int]            # start, end ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def short_name(hlo: str) -> str:
    """A device operation's name as the trace gives it is its HLO text;
    keep the instruction's name and its (first) result type:
    `%refine_topk.4 = (f32[128,1,128]`."""
    m = _SHORT.match(hlo)
    return m.group(0) if m else hlo[:120]


_SHORT = re.compile(r"^%[\w.\-]+ = \(?[a-z0-9]+\[[0-9,]*\]")


def read_xplane(trace_dir: str) -> Trace:
    """The reduced trace of the one `.xplane.pb` under `trace_dir`."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    pd = ProfileData.from_file(paths[0])
    short: Dict[str, str] = {}            # one regex match per distinct name
    ops: Dict[int, List[Event]] = {}
    host: List[Event] = []
    window = None
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                evs = ops.setdefault(int(m.group(1)), [])
                for e in line.events:
                    name = e.name
                    if name not in short:
                        short[name] = short_name(name)
                    evs.append((short[name], int(e.start_ns),
                                int(e.duration_ns)))
            elif plane.name == HOST_PLANE:
                for e in line.events:
                    ev = (e.name, int(e.start_ns), int(e.duration_ns))
                    if e.name == WINDOW_SPAN:
                        window = (ev[1], ev[1] + ev[2])
                    else:
                        host.append(ev)
    if window is None:
        raise RuntimeError(f"the trace holds no {WINDOW_SPAN!r} span")
    return Trace(ops, host, window)


def dump(trace: Trace, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump({"ops": {str(c): v for c, v in trace.ops.items()},
                   "host": trace.host, "window": list(trace.window)}, f)


def load(path: str) -> Trace:
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    return Trace({int(c): [tuple(e) for e in v]
                  for c, v in raw["ops"].items()},
                 [tuple(e) for e in raw["host"]], tuple(raw["window"]))


def _clipped(events: List[Event], lo: int, hi: int):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def busy_intervals(events: List[Event], lo: int, hi: int
                   ) -> List[Tuple[int, int]]:
    """The union of the events' intervals inside [lo, hi), merged and
    in order."""
    out: List[List[int]] = []
    for _, a, b in sorted(_clipped(events, lo, hi), key=lambda e: e[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(trace: Trace) -> float:
    """Seconds of the window in which some operation ran, averaged over
    the chips in the trace: each chip's busy union, summed, over the
    number of chips (a run on four chips reads a mean chip)."""
    if not trace.ops:
        return 0.0
    lo, hi = trace.window
    total = sum(sum(b - a for a, b in busy_intervals(ev, lo, hi))
                for ev in trace.ops.values())
    return total / len(trace.ops) / 1e9


def idle_share(trace: Trace) -> float:
    return 1.0 - busy_s(trace) / trace.window_s


def op_seconds(trace: Trace, pattern: str) -> Tuple[float, int]:
    """Summed device seconds, over every chip, of the operations inside
    the window whose name matches the regular expression `pattern`, and
    how many there were."""
    rx = re.compile(pattern)
    lo, hi = trace.window
    secs, n = 0, 0
    for ev in trace.ops.values():
        for name, a, b in _clipped(ev, lo, hi):
            if rx.search(name):
                secs += b - a
                n += 1
    return secs / 1e9, n


#: holes shorter than this are summed apart, not named one by one
MIN_GAP_NS = 10_000
SHORT_GAPS = "(gaps under 10 us)"


class _HostIndex:
    """Host events bucketed by the power of two of their duration, each
    bucket sorted by start, so the events overlapping [a, b) are found
    by one bisection per bucket."""

    def __init__(self, events: List[Event]):
        by: Dict[int, List[Event]] = {}
        for e in events:
            by.setdefault(max(int(e[2]), 1).bit_length(), []).append(e)
        self.buckets = []
        for c, evs in by.items():
            evs.sort(key=lambda e: e[1])
            self.buckets.append((1 << c, [e[1] for e in evs], evs))

    def overlapping(self, a: int, b: int):
        for span, starts, evs in self.buckets:
            i = bisect.bisect_left(starts, a - span)
            while i < len(evs) and evs[i][1] < b:
                name, s, d = evs[i]
                ov = min(b, s + d) - max(a, s)
                if ov > 0:
                    yield name, d, ov
                i += 1


def idle_gaps(trace: Trace, chip: Optional[int] = None
              ) -> List[Tuple[str, int]]:
    """(host activity, ns) for every hole of at least MIN_GAP_NS in the
    busy union of `chip` (the lowest id when None) inside the window,
    and the shorter holes summed under SHORT_GAPS.  The activity is the
    shortest host event that covers at least half of the hole, else the
    one that overlaps it most; "none" where no host event overlaps."""
    if not trace.ops:
        return []
    lo, hi = trace.window
    chip = min(trace.ops) if chip is None else chip
    busy = busy_intervals(trace.ops.get(chip, []), lo, hi)
    holes, t = [], lo
    for a, b in busy:
        if a > t:
            holes.append((t, a))
        t = max(t, b)
    if t < hi:
        holes.append((t, hi))
    short = sum(b - a for a, b in holes if b - a < MIN_GAP_NS)
    out = [(SHORT_GAPS, short)] if short else []
    host = _HostIndex(trace.host)
    for a, b in holes:
        if b - a < MIN_GAP_NS:
            continue
        best, best_key = "none", None
        for name, d, ov in host.overlapping(a, b):
            key = (0, d) if 2 * ov >= b - a else (1, -ov)
            if best_key is None or key < best_key:
                best, best_key = name, key
        out.append((best, b - a))
    return out


def self_times(events: List[Event], lo: int, hi: int) -> Dict[str, int]:
    """ns by operation name inside [lo, hi), each operation's time less
    the time of the operations nested in it (a while loop's body ops
    run inside the while's own event)."""
    out: Dict[str, int] = {}
    stack: List[List] = []                # [name, end, self ns]

    def pop():
        name, _, own = stack.pop()
        out[name] = out.get(name, 0) + own

    for name, a, b in sorted(_clipped(events, lo, hi),
                             key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            pop()
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([name, b, b - a])
    while stack:
        pop()
    return out


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device operations that took most time (self time, summed
    over chips), and the idle time summed by what the host was doing,
    in seconds, `top` of each."""
    lo, hi = trace.window
    by_op: Dict[str, int] = {}
    for ev in trace.ops.values():
        for name, ns in self_times(ev, lo, hi).items():
            by_op[name] = by_op.get(name, 0) + ns
    by_host: Dict[str, int] = {}
    for name, ns in idle_gaps(trace):
        by_host[name] = by_host.get(name, 0) + ns
    return {"device_ops": _ranked(by_op, top),
            "idle_gaps": _ranked(by_host, top)}


def _ranked(ns_by_name: Dict[str, int], top: int) -> list:
    return [[k, v / 1e9] for k, v in
            sorted(ns_by_name.items(), key=lambda kv: -kv[1])[:top]]
