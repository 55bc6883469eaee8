"""The rate sweep of a serve cell runs its windows at every rate asked
for, over one build, and sums them up by rate."""

from bench import sweep


def test_sweep_reports_every_rate(tiny_root, capsys):
    out = sweep.main(["--workload", "tiny-rw64.serve", "--rates", "20", "40",
                      "--seeds", "5", "6", "--seconds", "0.5"],
                     root=tiny_root, require_tpu=False)
    assert [r["rate"] for r in out] == [20.0, 40.0]
    assert all(r["windows"] == 2 for r in out)
    assert all(r["p95_min_ms"] <= r["p95_median_ms"] <= r["p95_max_ms"]
               for r in out)
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 4 + 2
