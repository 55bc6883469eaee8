#!/usr/bin/env python3
"""The sweep that finds the rate a serve cell offers: windows of the
cell's own mix at several rates, over one build of its index.

    python3 bench/sweep.py --workload <serve cell> --rates 32 40 48 \
        --seeds 1 2 3 4 --seconds 20

For each rate and seed, the loop's set-up and window run as a run of the
cell makes them, with "rate_per_s" replaced.  One JSON line per window
(the end-to-end metrics, the engine's counters and the loop's notes:
backlog at the last arrival, drain), then one line per rate: the median,
least and largest p95 over its windows and the largest backlog.  The
cell's mix file holds the rate chosen from it as a number; the
benchmark's own runs do not run this.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))

import dataclasses  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402


def window_at(run, loop, rate: float, seed: int, seconds: float) -> dict:
    """One window of `run`'s cell at `rate`, drawn from `seed`."""
    cell = run.cell
    run.cell = dataclasses.replace(cell, mix=dict(cell.mix, rate_per_s=rate))
    run.seed = seed
    try:
        loop.prepare(run, seconds)
        w = loop.measure(run, seconds)
        loop.close(run)
    finally:
        run.cell = cell
    c = w.counters
    return {"rate": rate, "seed": seed, "errors": w.errors,
            **{k: v for k, v in w.metrics.items()},
            "fill": c["completed"] / max(1, c["completed"]
                                         + c["padded_slots"]),
            "rounds_per_query": c["rounds_sum"] / max(1, c["completed"]),
            **w.notes}


def summary(rows) -> list:
    out = []
    for rate in sorted({r["rate"] for r in rows}):
        p95 = [r["latency_p95_ms"] for r in rows if r["rate"] == rate]
        out.append({"rate": rate, "windows": len(p95),
                    "p95_median_ms": statistics.median(p95),
                    "p95_min_ms": min(p95), "p95_max_ms": max(p95),
                    "backlog_max": max(r["backlog_at_last_arrival"]
                                       for r in rows if r["rate"] == rate)})
    return out


def main(argv=None, *, root: str = ROOT, require_tpu: bool = True) -> list:
    import argparse
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (default: run_seconds)")
    args = ap.parse_args(argv)
    cell, seconds = harness.tool_cell(args.workload, args.seconds, root,
                                      require_tpu)
    bench_dir = os.path.join(root, "bench")
    run, loop, _, _ = harness.set_up(cell, args.seeds[0], seconds, bench_dir)
    loop.close(run)
    rows = []
    for rate in args.rates:
        for seed in args.seeds:
            rows.append(window_at(run, loop, rate, seed, seconds))
            print(json.dumps(rows[-1]), flush=True)
    out = summary(rows)
    for line in out:
        print(json.dumps(line), flush=True)
    return out


if __name__ == "__main__":
    try:
        main()
    except RuntimeError as e:
        print(f"sweep: {e}", file=sys.stderr)
        sys.exit(2)
