"""A run whose timed path is broken underneath comes out not correct.

Each case drives the whole harness at test size on the CPU (the look
for a chip is skipped) with one fault planted where the answers are
produced: in `FreshIndex.search` for the batch cell, in the served
future's result for the serve cell.  The cell has no training state and
one chip, so the faults it can have are an answer altered, half of the
batch left out, and a stale answer handed back.  The control, put in
the program's place, comes out not correct too.
"""

import jax.numpy as jnp
import numpy as np
import pytest


def _alter_one_id(d, i):
    i = np.array(i)
    i.reshape(-1)[3] = (i.reshape(-1)[3] + 1) % 4096
    return d, i


def _alter_one_distance(d, i):
    d = np.array(d)
    d.reshape(-1)[3] *= 1.001
    return d, i


def _half_the_batch(d, i):
    """The first half's answers stand for the whole batch."""
    d, i = np.array(d), np.array(i)
    h = d.shape[0] // 2
    d[h:2 * h], i[h:2 * h] = d[:h], i[:h]
    return d, i


FAULTS = {"answer_altered": _alter_one_id,
          "distance_altered": _alter_one_distance,
          "half_batch": _half_the_batch}


@pytest.mark.parametrize("fault", sorted(FAULTS) + ["stale"])
def test_batch_fault_is_not_correct(run_tiny, monkeypatch, fault):
    from repro.api import FreshIndex
    orig = FreshIndex.search
    last = []

    def broken(self, queries, k=1, **kw):
        d, i = orig(self, queries, k=k, **kw)
        if fault == "stale":
            out = last[0] if last else (d, i)
            last[:] = [(d, i)]
            return out
        d, i = FAULTS[fault](np.asarray(d), np.asarray(i))
        return jnp.asarray(d), jnp.asarray(i)

    monkeypatch.setattr(FreshIndex, "search", broken)
    out = run_tiny("tiny-rw64.batch")
    assert out["correct"] is False
    assert out["failed"] > 0


@pytest.mark.parametrize("fault", ["answer_altered", "distance_altered",
                                   "stale"])
def test_serve_fault_is_not_correct(run_tiny, monkeypatch, fault):
    from repro.serve.engine import SearchFuture
    orig = SearchFuture.result
    calls = []

    def broken(self, timeout=None):
        d, i = orig(self, timeout=timeout)
        calls.append((d, i))
        if len(calls) % 5 == 0:
            if fault == "stale":
                return calls[-2]
            return FAULTS[fault](d, i)
        return d, i

    monkeypatch.setattr(SearchFuture, "result", broken)
    out = run_tiny("tiny-rw64.serve")
    assert out["correct"] is False
    assert out["failed"] > 0


def test_control_in_the_programs_place_is_not_correct(tiny_root, run_tiny,
                                                     monkeypatch):
    """The control (the reference one step below the configuration's
    precision) answering for `FreshIndex.search`."""
    from bench import harness, reference
    from repro.api import FreshIndex
    seed = 2**31 + 91
    parts = harness.series(
        harness.load_cell("tiny-rw64.batch", root=tiny_root), seed)

    def control(self, queries, k=1, **kw):
        d, i = reference.control_scan(parts, np.asarray(queries), k)
        return jnp.asarray(d), jnp.asarray(i)

    monkeypatch.setattr(FreshIndex, "search", control)
    out = run_tiny("tiny-rw64.batch", seed=seed)
    assert out["correct"] is False
    assert out["failed"] > 0
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_sound_runs_are_correct(run_tiny):
    assert run_tiny("tiny-rw64.batch")["correct"] is True
