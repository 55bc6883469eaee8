#!/usr/bin/env python3
"""Record a stretch of one traced run's reduced trace, as the CPU tests
of the reduction read it (`bench/traces/<cell>.json.gz`).

    python3 bench/record_trace.py --workload rw256-4m.batch-exact \
        --seed 1 --seconds 5 --out bench/traces/rw256-4m.batch-exact.json.gz

The run is a run of the cell with `--trace 1`.  Of its trace, `--ms`
milliseconds are kept, from `--lead-ms` before the second host span
named `--around` (the idle gap before a call, then the call's start);
the window becomes that stretch.  The benchmark's own runs do not run
this.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))

import tempfile  # noqa: E402
import time  # noqa: E402


def stretch(trace, lo: int, hi: int):
    """`trace` cut to [lo, hi): every event clipped to it, that as the
    window."""
    from bench import tracing

    def cut(events):
        return [(n, max(s, lo), min(s + d, hi) - max(s, lo))
                for n, s, d in events if min(s + d, hi) > max(s, lo)]
    return tracing.Trace({c: cut(ev) for c, ev in trace.ops.items()},
                         cut(trace.host), (lo, hi))


def main(argv=None, *, root: str = ROOT, require_tpu: bool = True):
    import argparse
    from bench import harness, tracing
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--around", default="bench.search")
    ap.add_argument("--lead-ms", type=float, default=1.0)
    ap.add_argument("--ms", type=float, default=27.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload, root=root)
    with tempfile.TemporaryDirectory() as tmp:
        full = os.path.join(tmp, "full.json.gz")
        harness.run_cell(cell, args.seed, args.seconds, True,
                         time.monotonic(), require_tpu=require_tpu,
                         root=root, keep_trace=full)
        trace = tracing.load(full)
    starts = sorted(s for n, s, _ in trace.host if n == args.around)
    lo = starts[1] - int(args.lead_ms * 1e6)
    out = stretch(trace, lo, lo + int(args.ms * 1e6))
    tracing.dump(out, args.out)
    return out


if __name__ == "__main__":
    try:
        main()
    except RuntimeError as e:
        print(f"record_trace: {e}", file=sys.stderr)
        sys.exit(2)
