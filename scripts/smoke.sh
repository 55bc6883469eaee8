#!/usr/bin/env bash
# End-to-end smoke: build -> k-NN search -> add/compact -> save/load via
# the FreshIndex facade, on whatever backend jax finds (CPU in CI), a
# DeprecationWarning-as-error pytest leg over the index test files, then
# a 2-figure benchmark subset (fig3 query + fig5 scaling, both kernel
# backends) PLUS the serving legs (--serve-quick: local QueryEngine and
# the SHARDED engine on a forced 2-device host mesh, both driven by a
# Poisson arrival stream, plus the overload sweep — bounded admission
# vs unbounded baseline at 0.5x-3x saturation) AND the build-pipeline
# leg (--build-quick:
# IndexBuilder single-shot vs multi-worker vs crash-injected, compact
# merge vs rebuild) AND the lifecycle maintenance leg (--maint-quick:
# tombstone-mask search overhead, compaction reclaim rate, TTL sweep
# cost) AND the recall-tiered approximate-search leg (--quality-quick:
# calibrated recall@k >= target, approx p99 < exact p99 on one
# latency-tiered engine) AND the refine-kernel autotune leg
# (--autotune-quick: tiny bitwise-gated sweep on the live device and
# the AutotuneTable JSON write) at --quick scale,
# emitting the machine-readable BENCH_fresh.json perf record with
# p50/p99 latency + QPS rows.
#
#   scripts/smoke.sh                  full smoke
#   scripts/smoke.sh --sharded-serve  only the sharded serving leg:
#                                     2-device example + serve/sharded/*
#                                     row validation of the committed
#                                     BENCH_fresh.json
#   scripts/smoke.sh --autotune-quick only the autotune leg: tiny sweep
#                                     to a scratch JSON + kernels/* row
#                                     + table-write validation
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

SHARDED_ONLY=0
AUTOTUNE_ONLY=0
for a in "$@"; do
    case "$a" in
        --sharded-serve) SHARDED_ONLY=1 ;;
        --autotune-quick) AUTOTUNE_ONLY=1 ;;
        *) echo "unknown flag: $a" >&2; exit 2 ;;
    esac
done

run_sharded_example() {
    # 2-device CPU host mesh: the sharded engine example end to end
    # (AOT mesh plans, mesh-wide epochs, helping, elastic recovery)
    XLA_FLAGS=--xla_force_host_platform_device_count=2 \
        python examples/serve_sharded.py
}

validate_sharded_rows() {
    python - <<'EOF'
import json
rows = json.load(open("BENCH_fresh.json"))["rows"]
sharded = [r for r in rows if r["name"].startswith("serve/sharded/")]
names = {r["name"] for r in sharded}
assert "serve/sharded/warmup_aot_compile" in names, names
assert "serve/sharded/poisson/steady" in names, names
steady = next(r for r in sharded
              if r["name"] == "serve/sharded/poisson/steady")
for key in ("p50_us", "p99_us", "qps", "plan_hits", "plan_misses"):
    assert key in steady, ("serve/sharded/poisson/steady", key)
assert "mesh=data:2" in steady["derived"], steady["derived"]
print("serve/sharded/* rows OK "
      f"(qps={steady['qps']}, p50={steady['p50_us']}us, "
      f"misses={steady['plan_misses']})")
EOF
}

validate_autotune_rows() {
    # $1: the bench JSON to check (defaults to the committed record).
    # Asserts the kernels/* rows exist, the sweep's winner survived the
    # bitwise exactness gate, and the AutotuneTable JSON was written
    # non-empty.
    BENCH_JSON="${1:-BENCH_fresh.json}" python - <<'EOF'
import json
import os

path = os.environ["BENCH_JSON"]
rows = json.load(open(path))["rows"]
by_name = {r["name"]: r for r in rows}
for name in ("kernels/refine/autotune/baseline",
             "kernels/refine/autotune/winner",
             "kernels/refine/autotune/table"):
    assert name in by_name, f"missing {name} row in {path}"
win = by_name["kernels/refine/autotune/winner"]
assert 1 <= win["n_exact"] <= win["n_candidates"], (
    "no candidate survived the bitwise gate", win)
assert win["speedup"] > 0, win
table_path = by_name["kernels/refine/autotune/table"]["path"]
assert os.path.exists(table_path), (
    "autotune table JSON not written", table_path)
table = json.load(open(table_path))
assert table.get("entries"), ("autotune table written empty", table_path)
assert table.get("fingerprint"), ("table missing fingerprint", table_path)
print(f"kernels/* rows OK (winner speedup={win['speedup']}x, "
      f"{win['n_exact']}/{win['n_candidates']} candidates bit-exact, "
      f"table={table_path} "
      f"entries={len(table['entries'])})")
EOF
}

run_autotune_quick() {
    # tiny sweep on the live device to a scratch JSON (doesn't clobber
    # the committed BENCH_fresh.json): exercises the bitwise gate, the
    # AutotuneTable write end to end
    python -m benchmarks.run --only kernels --quick --autotune-quick \
        --json /tmp/bench_autotune.json
    validate_autotune_rows /tmp/bench_autotune.json
}

if [ "$SHARDED_ONLY" = 1 ]; then
    run_sharded_example
    validate_sharded_rows
    exit 0
fi

if [ "$AUTOTUNE_ONLY" = 1 ]; then
    run_autotune_quick
    exit 0
fi

# Concurrency gates (docs/ANALYSIS.md): the AST lint must be clean
# modulo the justified .lint-allow entries, and a quick-budget schedule
# exploration must hold every invariant (exactly-once, bit-identity,
# snapshot immutability, lock-freedom under permanent stalls).  The
# full >=10k-interleaving run is `python -m repro.analysis.checker`.
python -m repro.analysis.lint src/
python -m repro.analysis.checker --budget 400

python examples/quickstart.py
python examples/serve_engine.py
run_sharded_example

# DeprecationWarning-clean leg: the data-series-index test files (the
# former shim call sites) must pass with deprecations promoted to errors
# — only pytest.warns-guarded shim-coverage calls may emit them.
python -W error::DeprecationWarning -m pytest -q -x \
    tests/test_api.py tests/test_builder.py tests/test_index_search.py \
    tests/test_docs.py tests/test_system.py

python -m benchmarks.run --only fig3,fig5,serve,build,maint,quality,kernels \
    --quick --serve-quick --build-quick --maint-quick --quality-quick \
    --autotune-quick --json BENCH_fresh.json
python - <<'EOF'
import json
rows = json.load(open("BENCH_fresh.json"))["rows"]
for fig, bk in (("fig3", "ref"), ("fig3", "pallas"),
                ("fig5", "ref"), ("fig5", "pallas")):
    assert any(r["name"].startswith(fig) and r["name"].endswith("/" + bk)
               and "per_query_us" in r for r in rows), (fig, bk)
serve = [r for r in rows if r["name"].startswith("serve/poisson")]
assert serve, "no serve/poisson rows in BENCH_fresh.json"
for r in serve:
    for key in ("p50_us", "p99_us", "qps"):
        assert key in r, (r["name"], key)
assert any(r["name"] == "serve/warmup_aot_compile" for r in rows)
# overload sweep: bounded admission keeps admitted-query p99 and goodput
# flat past the saturation knee (noise-tolerant bounds: the strict
# within-20% claim is for quiet hardware; see EXPERIMENTS.md §Serving)
# while the unbounded baseline's p99 diverges with offered load
ov = {r["name"]: r for r in rows
      if r["name"].startswith("serve/overload/")}
for name in ("serve/overload/bounded/x0.5", "serve/overload/bounded/x1.0",
             "serve/overload/bounded/x2.0", "serve/overload/bounded/x3.0",
             "serve/overload/unbounded/x1.0",
             "serve/overload/unbounded/x3.0", "serve/overload/cached/x3.0"):
    assert name in ov, f"missing {name} row"
    for key in ("goodput_qps", "shed_rate", "p99_us", "delivered"):
        assert key in ov[name], (name, key)
b1, b3 = ov["serve/overload/bounded/x1.0"], ov["serve/overload/bounded/x3.0"]
u3 = ov["serve/overload/unbounded/x3.0"]
assert b3["p99_us"] <= 1.5 * b1["p99_us"], (
    "bounded p99 not flat past the knee", b1["p99_us"], b3["p99_us"])
assert b3["goodput_qps"] >= 0.6 * b1["goodput_qps"], (
    "bounded goodput collapsed past the knee",
    b1["goodput_qps"], b3["goodput_qps"])
assert b3["shed_rate"] > 0.2, ("3x overload must shed", b3["shed_rate"])
assert u3["shed_rate"] == 0 and u3["p99_us"] > 1.5 * b3["p99_us"], (
    "unbounded baseline p99 must diverge above bounded",
    u3["p99_us"], b3["p99_us"])
assert "cache_hits=0" not in ov["serve/overload/cached/x3.0"]["derived"], (
    "cached overload leg recorded no cache hits")
# build pipeline rows: single-shot vs builder vs crash-injected, plus
# compact incremental-merge vs full-rebuild (merge must win)
by_name = {r["name"]: r for r in rows}
for name in ("build/oneshot_fused", "build/pipeline/seq",
             "build/pipeline/w4", "build/pipeline/w4_crash",
             "build/compact/merge", "build/compact/rebuild"):
    assert name in by_name, f"missing {name} row"
assert "bit_identical=1" in by_name["build/pipeline/w4_crash"]["derived"]
merge = by_name["build/compact/merge"]["us_per_call"]
rebuild = by_name["build/compact/rebuild"]["us_per_call"]
assert merge < rebuild, (merge, rebuild)
# lifecycle maintenance rows: tombstone-mask overhead, physical reclaim,
# TTL sweep (docs/SERVING.md "Maintenance & freshness tiers")
assert "overhead_pct" in by_name["maint/mask_overhead"]
reclaim = by_name["maint/compact_reclaim"]
assert reclaim["reclaim_rate"] > 0 and reclaim["rows_per_s"] > 0, reclaim
assert "per_entry_us" in by_name["maint/ttl_sweep"]
# quality rows: the exact-tier baseline plus one row per calibrated
# recall target; measured recall must meet the target and the approx
# tier must beat its OWN engine's exact p99 (the committed full-scale
# record makes the stronger <=0.6x claim — see EXPERIMENTS.md
# §Approximate search)
assert "p99_us" in by_name["quality/exact"], by_name.keys()
qrows = [r for r in rows if r["name"].startswith("quality/approx/")]
assert qrows, "no quality/approx/* rows in BENCH_fresh.json"
for r in qrows:
    assert r["recall_at_k"] >= r["recall_target"], (
        "calibrated recall below target", r["name"],
        r["recall_at_k"], r["recall_target"])
    assert 0.0 < r["visited_frac"] < 1.0, (
        "approx tier did not early-terminate", r["name"],
        r["visited_frac"])
    assert r["p99_us"] < r["exact_p99_us"], (
        "approx p99 not below exact p99 on the same engine",
        r["name"], r["p99_us"], r["exact_p99_us"])
q95 = by_name.get("quality/approx/0.95")
assert q95 is not None, "missing the 0.95-target quality row"
print(f"BENCH_fresh.json OK: {len(rows)} rows; fig3+fig5 both backends, "
      f"serve p50/p99/QPS, overload sweep (bounded p99 "
      f"{b3['p99_us']/b1['p99_us']:.2f}x 1x->3x, unbounded "
      f"{u3['p99_us']/b3['p99_us']:.2f}x above), build pipeline+compact "
      f"rows present (merge {rebuild/merge:.2f}x faster than rebuild), "
      f"maint mask overhead "
      f"{by_name['maint/mask_overhead']['overhead_pct']}%")
EOF
validate_sharded_rows
validate_autotune_rows BENCH_fresh.json
