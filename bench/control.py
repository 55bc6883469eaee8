#!/usr/bin/env python3
"""The readings that the limits of the check are set from: the
program's over many seeds, and the control's.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 --seconds 20

For each seed, set-up and window are made as a run of the cell makes
them.  Then the reference compares the window's answers (the program's
reading) and, on the same queries, the reference computed in the
control's precision, one step below the configuration's
(`reference.control_scan`: the control's reading).  One JSON line per
seed, then for each number the largest program reading and the
smallest control reading.  The benchmark's own runs do not run this.
"""

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))

import json  # noqa: E402

NUMBERS = ("dist_err", "rank_gap")


def readings(cell, seed: int, seconds: float, bench_dir: str) -> dict:
    from bench import harness, reference
    run, loop, _, _ = harness.set_up(cell, seed, seconds, bench_dir)
    window = loop.measure(run, seconds)
    harness.tear_down(run, loop)
    x = harness.series(cell, seed)
    k = int(cell.mix["k"])
    ref = reference.Reference(x, window.queries, k)
    prog = reference.compare(x, window.queries, ref, window.dist,
                             window.ids)
    ctl = reference.compare(x, window.queries, ref,
                            *reference.control_scan(x, window.queries, k))
    return {"seed": seed, "answers": int(window.queries.shape[0]),
            "unsure": ref.unsure, "errors": window.errors,
            "program": {n: float(v.max()) for n, v in zip(NUMBERS, prog)},
            "control": {n: float(v.max()) for n, v in zip(NUMBERS, ctl)}}


def summary(rows) -> dict:
    return {n: {"program_max": max(r["program"][n] for r in rows),
                "control_min": min(r["control"][n] for r in rows)}
            for n in NUMBERS}


def main(argv=None, *, root: str = ROOT, require_tpu: bool = True) -> dict:
    import argparse
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (default: run_seconds)")
    args = ap.parse_args(argv)
    cell, seconds = harness.tool_cell(args.workload, args.seconds, root,
                                      require_tpu)
    rows = []
    for seed in args.seeds:
        t = time.perf_counter()
        rows.append(readings(cell, seed, seconds,
                             os.path.join(root, "bench")))
        rows[-1]["seconds"] = time.perf_counter() - t
        print(json.dumps(rows[-1]), flush=True)
    out = summary(rows)
    print(json.dumps({"workload": args.workload, "summary": out}),
          flush=True)
    return out


if __name__ == "__main__":
    try:
        main()
    except RuntimeError as e:
        print(f"control: {e}", file=sys.stderr)
        sys.exit(2)
