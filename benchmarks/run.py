"""Benchmark harness entry point: one bench per paper table/figure, plus
the roofline tables derived from the multi-pod dry-run.

    PYTHONPATH=src python -m benchmarks.run [--only fig3,fig7]
                                            [--json BENCH_fresh.json]
                                            [--quick]

Prints ``name,us_per_call,derived`` CSV rows, then the roofline summary.
--json additionally writes every figure as machine-readable JSON (rows +
meta) so the perf trajectory is tracked across PRs; --quick shrinks the
dataset/query counts to the CI smoke scale (scripts/smoke.sh).
"""

from __future__ import annotations

import argparse
import json
import platform
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list of bench prefixes (fig3,fig5,...)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as machine-readable JSON "
                         "(BENCH_fresh.json)")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke scale: fewer series/queries")
    ap.add_argument("--serve-quick", action="store_true",
                    help="also drive the QueryEngine with a Poisson "
                         "arrival stream (serve/* rows: p50/p99 + QPS)")
    ap.add_argument("--build-quick", action="store_true",
                    help="also run the IndexBuilder pipeline bench "
                         "(build/* rows: single-shot vs builder vs "
                         "crash-injected, compact merge vs rebuild)")
    ap.add_argument("--maint-quick", action="store_true",
                    help="also run the lifecycle maintenance bench "
                         "(maint/* rows: tombstone-mask search overhead, "
                         "compaction reclaim rate, TTL sweep cost)")
    ap.add_argument("--quality-quick", action="store_true",
                    help="also run the recall-tiered approximate-search "
                         "bench (quality/* rows: calibrated recall@k, "
                         "visited-leaf fraction, approx vs exact p99 on "
                         "one latency-tiered engine)")
    ap.add_argument("--autotune-quick", action="store_true",
                    help="also run the refine-kernel autotune sweep "
                         "(kernels/* rows: bitwise-gated winner vs "
                         "baseline and the AutotuneTable write)")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    from . import fresh_bench
    from . import roofline_table
    from .common import fmt_row

    if args.quick:
        fresh_bench.set_quick()

    only = set(args.only.split(",")) if args.only else None
    print("name,us_per_call,derived")
    t0 = time.time()
    failures = 0
    rows = []
    benches = list(fresh_bench.ALL)
    if args.serve_quick:
        from . import serve_bench
        if args.quick:
            serve_bench.set_quick()
        benches += serve_bench.ALL
    if args.build_quick:
        from . import build_bench
        if args.quick:
            build_bench.set_quick()
        benches += build_bench.ALL
    if args.maint_quick:
        from . import maintenance_bench
        if args.quick:
            maintenance_bench.set_quick()
        benches += maintenance_bench.ALL
    if args.quality_quick:
        from . import quality_bench
        if args.quick:
            quality_bench.set_quick()
        benches += quality_bench.ALL
    if args.autotune_quick:
        from . import kernels_bench
        if args.quick:
            kernels_bench.set_quick()
        benches += kernels_bench.ALL
    for fn in benches:
        tag = fn.__name__.split("_")[0]
        if only and tag not in only:
            continue
        try:
            for r in fn():
                rows.append(r)
                print(fmt_row(r), flush=True)
        except Exception as e:       # pragma: no cover
            failures += 1
            print(f"{fn.__name__},nan,ERROR:{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)

    print(f"# benches done in {time.time()-t0:.1f}s", flush=True)
    print("#")
    print("# ---- refine-round roofline (fused kernel vs materializing) ----")
    for line in roofline_table.refine_rows():
        print(f"# {line}")
    print("#")
    print("# ---- multi-pod dry-run / roofline summary ----")
    for line in roofline_table.summary():
        print(f"# {line}")
    print("#")
    print("# ---- roofline table (single pod, 16x16) ----")
    for line in roofline_table.table(multi=False):
        print(f"# {line}")

    if args.json:
        import jax
        payload = {
            "meta": {
                "quick": bool(args.quick),
                "n_series": fresh_bench.N_SERIES,
                "n_queries": fresh_bench.N_QUERIES,
                "backends": list(fresh_bench.BACKENDS),
                "jax_backend": jax.default_backend(),
                "jax_version": jax.__version__,
                "python": platform.python_version(),
                "wall_seconds": round(time.time() - t0, 1),
                "note": ("interpret-mode pallas timings on CPU are "
                         "correctness traces, not hardware perf — "
                         "see EXPERIMENTS.md"),
            },
            "rows": rows,
        }
        with open(args.json, "w") as fh:
            json.dump(payload, fh, indent=1, sort_keys=True)
            fh.write("\n")
        print(f"# wrote {len(rows)} rows to {args.json}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
