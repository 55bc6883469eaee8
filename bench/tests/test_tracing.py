"""The reduction from trace to metrics, on a trace recorded on one TPU
v5e: 27 ms of `rw256-4m.batch-exact` around the start of a search (the
idle gap between two searches, the bound kernel, the PQ sort and the
first refine rounds)."""

import os

import numpy as np
import pytest

from bench import harness, tracing

from .conftest import BENCH

RECORDED = os.path.join(BENCH, "traces", "rw256-4m.batch-exact.json.gz")


@pytest.fixture(scope="module")
def trace():
    return tracing.load(RECORDED)


def _timeline(trace):
    """Busy ns, counted on an independent 1 us grid of the window."""
    lo, hi = trace.window
    grid = np.zeros((hi - lo) // 1000 + 1, bool)
    for _, s, d in trace.ops[0]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            grid[(a - lo) // 1000:(b - lo + 999) // 1000] = True
    return grid.sum() * 1000


def test_busy_union_against_a_grid(trace):
    busy_ns = tracing.busy_s(trace) * 1e9
    assert abs(busy_ns - _timeline(trace)) < 2000 * 40   # 1 us rounding
    assert 0 < tracing.busy_s(trace) < trace.window_s


def test_idle_share_and_gaps_add_up(trace):
    idle = tracing.idle_share(trace)
    assert idle == pytest.approx(1 - tracing.busy_s(trace) / trace.window_s)
    gaps = tracing.idle_gaps(trace)
    assert sum(ns for _, ns in gaps) / 1e9 == pytest.approx(
        trace.window_s - tracing.busy_s(trace), rel=1e-9)
    # the one long gap lies between two searches, while the host is
    # inside the harness's search span
    name, ns = max(gaps, key=lambda g: g[1])
    assert name == "bench.search" and ns > 1_000_000


def test_kernel_time_by_name(trace):
    lb, n_lb = tracing.op_seconds(trace, r"^%lb_distance\.")
    refine, n_refine = tracing.op_seconds(trace, r"^%refine_topk\.")
    assert n_lb == 1 and lb == pytest.approx(187947e-9)
    lo, hi = trace.window
    want = sum(min(s + d, hi) - max(s, lo) for name, s, d in trace.ops[0]
               if name.startswith("%refine_topk.") and s < hi and s + d > lo)
    assert n_refine == 35 and refine == pytest.approx(want / 1e9)


def test_breakdown_self_times(trace):
    b = tracing.breakdown(trace)
    assert len(b["device_ops"]) == 10 and b["device_ops"][0][0].startswith(
        "%refine_topk.")
    lo, hi = trace.window
    total = sum(tracing.self_times(trace.ops[0], lo, hi).values()) / 1e9
    # self times of nested ops add up to the busy union
    assert total == pytest.approx(tracing.busy_s(trace), rel=1e-6)


def test_lb_distance_roofline_arithmetic(trace):
    mod = harness.load_module(BENCH, "metrics", "lb_distance_roofline.batch")
    peaks = harness.peaks_for("TPU v5 lite", BENCH)
    least = mod.least_seconds(128, 65536, 16, peaks)
    # bytes bound it: two (65536, 16) float32 regions and (128, 16) PAAs
    assert least == pytest.approx(4 * (2 * 65536 * 16 + 128 * 16) / 819e9)
    assert least > 4 * 128 * 65536 * 16 / 197e12

    class Stub:
        pass
    r = Stub()
    r.trace = trace
    r.cell = Stub()
    r.cell.mix = {"batch": 128}
    r.window = Stub()
    r.window.counters = {"calls": 1}
    r.shapes = {"n_leaves": 65536, "segments": 16}
    r.peaks = lambda: peaks
    assert mod.read(r) == pytest.approx(100 * least / 187947e-9)


def test_refine_ms_per_query(trace):
    mod = harness.load_module(BENCH, "metrics", "refine_ms_per_query.batch")

    class Stub:
        pass
    r = Stub()
    r.trace = trace
    r.window = Stub()
    r.window.counters = {"queries": 128}
    refine, _ = tracing.op_seconds(trace, r"^%refine_topk\.")
    assert mod.read(r) == pytest.approx(1e3 * refine / 128)


def test_a_metric_with_nothing_to_read_returns_none(trace):
    empty = tracing.Trace({0: []}, [], trace.window)

    class Stub:
        pass
    r = Stub()
    r.trace = empty
    r.window = Stub()
    r.window.counters = {"queries": 128, "calls": 1}
    for name in ("lb_distance_roofline.batch", "refine_ms_per_query.batch"):
        assert harness.load_module(BENCH, "metrics", name).read(r) is None


def test_a_recorded_stretch_reduces_as_a_trace(tiny_root, tmp_path):
    from bench import record_trace
    out = str(tmp_path / "tiny.json.gz")
    rec = record_trace.main(["--workload", "tiny-rw64.batch", "--seed", "3",
                             "--seconds", "0.5", "--out", out, "--ms", "50"],
                            root=tiny_root, require_tpu=False)
    back = tracing.load(out)
    assert back.window == rec.window
    assert back.window[1] - back.window[0] == 50_000_000
    lo, hi = back.window
    assert all(lo <= s and s + d <= hi
               for events in [back.host, *back.ops.values()]
               for _, s, d in events)
    assert 0 <= tracing.busy_s(back) <= back.window_s


def test_four_chip_trace():
    """A synthetic trace of four chips: busy time and the idle share are
    a mean chip's, kernel time is summed over the chips, and the idle
    gaps are read from the lowest chip id."""
    us = 1000
    window = (0, 100 * us)
    ops = {
        # chip 2 lists first: the lowest id, not the first listed, names
        # the gaps
        2: [("%refine_topk.1", 0, 100 * us)],
        0: [("%refine_topk.1", 10 * us, 30 * us),
            ("%fusion.3", 20 * us, 30 * us),       # overlaps the refine
            ("%lb_distance.2", 70 * us, 10 * us)],
        1: [("%refine_topk.1", 0, 50 * us)],
        3: [("%refine_topk.1", 90 * us, 20 * us)],  # runs past the window
    }
    host = [("bench.search", 0, 100 * us), ("fresh.search", 40 * us, 30 * us)]
    tr = tracing.Trace(ops, host, window)
    busy = {0: 50, 1: 50, 2: 100, 3: 10}                 # us, each chip
    assert tracing.busy_s(tr) == pytest.approx(
        sum(busy.values()) / 4 * 1e-6)
    assert tracing.idle_share(tr) == pytest.approx(1 - 52.5 / 100)
    refine, n = tracing.op_seconds(tr, r"^%refine_topk\.")
    assert n == 4 and refine == pytest.approx((30 + 50 + 100 + 10) * 1e-6)
    # chip 0's holes: 0-10 us, 50-70 us (inside fresh.search, which covers
    # at least half of it and is the shortest such), 80-100 us
    assert tracing.idle_gaps(tr) == [("bench.search", 10 * us),
                                     ("fresh.search", 20 * us),
                                     ("bench.search", 20 * us)]
    b = tracing.breakdown(tr)
    # self time, over chips: on chip 0 the fusion takes 20-40 us of the
    # refine's 10-40 us
    assert dict(b["device_ops"])["%refine_topk.1"] == pytest.approx(
        (10 + 50 + 100 + 10) * 1e-6)
    assert b["idle_gaps"] == [["bench.search", 30e-6],
                              ["fresh.search", 20e-6]]
