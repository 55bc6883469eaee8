"""Backend autotune subsystem (repro.kernels.autotune) + the typed
lowering dispatch (`kernels._compat.resolve_lowering`).

The acceptance criteria of the autotune PR, machine-checked:

* table persistence — AutotuneTable round-trips through to_dict /
  save_json AND through the FreshIndex checkpoint (save/load/reload);
* fingerprint staleness refusal — any index mutation makes the table
  stale and `search_knobs()` falls back to the static defaults
  (mirroring `quality.CalibrationTable`, but CONSERVATIVE: a stale
  autotune table is never resolved through);
* unknown-device fallback — a table with no entry for the live
  (device_kind, L, leaf_capacity, dtype) key resolves to today's
  defaults, so an untuned device behaves exactly as before autotune
  existed;
* tuned == untuned — installing a swept table never changes any search
  result bit (the sweep gates every candidate on bitwise equality with
  the default-knob output on BOTH backends), for k in {1, 5, 10};
* the per-platform `resolve_lowering` matrix, including the typed
  `KernelLoweringError` when `backend="pallas"` has no lowering path.
"""

import numpy as np
import pytest

from repro.api import FreshIndex, IndexConfig
from repro.data.synthetic import query_workload, random_walk
from repro.kernels._compat import KernelLoweringError, resolve_lowering
from repro.kernels.autotune import (DEFAULTS, AutotuneTable, TuneConfig,
                                    TuneEntry, candidate_space, device_kind,
                                    resolve_knobs)
from repro.quality import index_fingerprint

L = 64
N = 256

# a tiny explicit sweep: default + one non-default per swept knob, so
# the module-scoped fixture tunes in seconds on the CPU interpreter
CANDS = (TuneConfig(),
         TuneConfig(round_leaves=16, dma_depth=2),
         TuneConfig(round_leaves=4))


@pytest.fixture(scope="module")
def data():
    walks = random_walk(N, L, seed=81)
    queries = query_workload(walks, 8, noise_sigma=0.05, seed=82)
    return walks, queries


@pytest.fixture(scope="module")
def tuned(data):
    """One untuned index + one autotuned twin built from the same rows."""
    walks, queries = data
    cfg = IndexConfig(leaf_capacity=8, backend="pallas")
    plain = FreshIndex.build(walks, cfg)
    ix = FreshIndex.build(walks, cfg)
    table = ix.autotune(queries=queries, k=5, repeat=1, candidates=CANDS)
    return plain, ix, table


def _entry(rl=16, dd=2):
    return TuneEntry(config=TuneConfig(round_leaves=rl, dma_depth=dd),
                     median_ms=1.0, baseline_ms=2.0,
                     n_candidates=3, n_exact=3)


# --------------------------------------------------------------------- #
# table persistence
# --------------------------------------------------------------------- #
def test_table_roundtrip_dict_and_json(tmp_path):
    t = AutotuneTable("fp-abc123")
    t.put("TPU v4", 128, 16, "float32", _entry())
    t.put("cpu", 64, 8, "float32", _entry(rl=8, dd=1))
    path = str(tmp_path / "table.json")
    t.save_json(path)
    for back in (AutotuneTable.from_dict(t.to_dict()),
                 AutotuneTable.load_json(path)):
        assert back.fingerprint == t.fingerprint
        assert len(back) == 2
        assert back.to_dict() == t.to_dict()
        e = back.lookup("TPU v4", 128, 16, "float32")
        assert e.config == TuneConfig(round_leaves=16, dma_depth=2)
        assert e.baseline_ms == 2.0 and e.n_exact == 3


def test_tuneconfig_from_dict_ignores_unknown_keys():
    d = TuneConfig(round_leaves=16).to_dict()
    d["future_knob"] = 7                     # forward compat
    assert TuneConfig.from_dict(d) == TuneConfig(round_leaves=16)


def test_checkpoint_roundtrip_preserves_table(tmp_path, tuned):
    _, ix, table = tuned
    assert ix.is_autotune_fresh()
    ix.save(str(tmp_path))
    ld = FreshIndex.load(str(tmp_path))
    assert ld.autotune_table is not None
    assert ld.autotune_table.fingerprint == table.fingerprint
    assert ld.autotune_table.to_dict() == table.to_dict()
    assert ld.is_autotune_fresh()
    assert ld.search_knobs() == ix.search_knobs()
    # reload() on a live index adopts the checkpoint's table too
    other = FreshIndex.build(random_walk(N, L, seed=83), ix.config)
    other.reload(str(tmp_path))
    assert other.autotune_table.to_dict() == table.to_dict()


# --------------------------------------------------------------------- #
# staleness refusal (mirrors CalibrationTable, but falls back)
# --------------------------------------------------------------------- #
def test_stale_table_is_not_resolved_through(data):
    walks, queries = data
    ix = FreshIndex.build(walks, IndexConfig(leaf_capacity=8,
                                             backend="pallas"))
    ix.autotune(queries=queries, k=5, repeat=1, candidates=CANDS)
    assert ix.is_autotune_fresh()
    ix.add(random_walk(4, L, seed=84))       # mutate -> fingerprint moves
    assert not ix.is_autotune_fresh()
    assert ix.search_knobs() == resolve_knobs(ix.config, None), (
        "stale autotune table must fall back to the static defaults")


# --------------------------------------------------------------------- #
# resolution chain: config field > fresh entry > DEFAULTS
# --------------------------------------------------------------------- #
def test_resolve_knobs_defaults_when_nothing_set():
    assert resolve_knobs(None, None) == TuneConfig(**DEFAULTS)
    assert resolve_knobs(IndexConfig(), None) == TuneConfig(**DEFAULTS)


def test_resolve_knobs_config_beats_table_beats_defaults():
    e = _entry(rl=16, dd=2)
    cfg = IndexConfig(round_leaves=32)       # explicit beats tuned
    got = resolve_knobs(cfg, e)
    assert got.round_leaves == 32
    assert got.dma_depth == 2                # unset -> tuned entry
    assert got.pq_budget is None             # unset, entry default
    assert resolve_knobs(None, e).round_leaves == 16


def test_unknown_device_falls_back_to_defaults(data):
    walks, _ = data
    ix = FreshIndex.build(walks, IndexConfig(leaf_capacity=8))
    t = AutotuneTable(index_fingerprint(ix))
    t.put("martian-npu", L, 8, "float32", _entry(rl=16, dd=4))
    ix._autotune = t                         # fresh fingerprint, wrong key
    assert ix.is_autotune_fresh()
    assert t.lookup(device_kind(), L, 8, "float32") is None
    assert ix.search_knobs() == TuneConfig(**DEFAULTS), (
        "a device the sweep never ran on must serve today's defaults")


# --------------------------------------------------------------------- #
# candidate space
# --------------------------------------------------------------------- #
def test_candidate_space_shape():
    full = candidate_space()
    quick = candidate_space(quick=True)
    assert full[0] == TuneConfig() and quick[0] == TuneConfig()
    assert len(set(full)) == len(full)   # deduped
    assert len(quick) < len(full)
    for swept in ("dma_depth", "round_leaves"):
        assert any(getattr(c, swept) != DEFAULTS[swept] for c in full)


# --------------------------------------------------------------------- #
# tuned == untuned, bit for bit (k in {1, 5, 10}, both backends)
# --------------------------------------------------------------------- #
def test_sweep_gates_candidates_and_records_evidence(tuned):
    _, ix, table = tuned
    ((key, entry),) = table.items()
    assert key == (device_kind(), L, ix.config.leaf_capacity,
                   ix.config.dtype)
    assert entry.n_candidates == len(CANDS)
    assert 1 <= entry.n_exact <= entry.n_candidates
    assert entry.median_ms > 0 and entry.baseline_ms > 0
    assert table.fingerprint == index_fingerprint(ix)


def test_autotuned_search_is_bit_identical_to_untuned(data, tuned):
    _, queries = data
    plain, ix, _ = tuned
    assert ix.is_autotune_fresh()
    for k in (1, 5, 10):
        for bk in ("pallas", "ref"):
            d0, i0 = plain.search(queries, k=k, backend=bk)
            d1, i1 = ix.search(queries, k=k, backend=bk)
            assert np.asarray(d0).tobytes() == np.asarray(d1).tobytes(), (
                "tuned search changed distance bits", k, bk)
            assert np.asarray(i0).tobytes() == np.asarray(i1).tobytes(), (
                "tuned search changed result ids", k, bk)


def test_installed_nondefault_knobs_stay_bit_identical(data, tuned):
    """Force a NON-default tuned entry (the sweep winner may tie with
    the default) and prove the served answers still match bitwise."""
    _, queries = data
    plain, _, _ = tuned
    ix = FreshIndex.build(random_walk(N, L, seed=81),
                          IndexConfig(leaf_capacity=8, backend="pallas"))
    t = AutotuneTable(index_fingerprint(ix))
    t.put(device_kind(), L, 8, ix.config.dtype, _entry(rl=16, dd=2))
    ix._autotune = t
    kn = ix.search_knobs()
    assert (kn.round_leaves, kn.dma_depth) == (16, 2)
    for k in (1, 5, 10):
        d0, i0 = plain.search(queries, k=k)
        d1, i1 = ix.search(queries, k=k)
        assert np.asarray(d0).tobytes() == np.asarray(d1).tobytes(), k
        assert np.asarray(i0).tobytes() == np.asarray(i1).tobytes(), k


# --------------------------------------------------------------------- #
# resolve_lowering: per-platform dispatch matrix + typed errors
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("platform,expect", [
    ("cpu", ("mosaic", True)),               # interprets by design
    ("tpu", ("mosaic", False)),
])
def test_resolve_lowering_default_matrix(platform, expect):
    assert resolve_lowering(platform=platform) == expect


@pytest.mark.parametrize("platform", ["gpu", "cuda", "rocm", "metal",
                                      "neuron", "weird-accel"])
def test_no_lowering_path_raises_typed_error(platform):
    for interpret in (None, False):
        with pytest.raises(KernelLoweringError) as ei:
            resolve_lowering(interpret=interpret, platform=platform)
        msg = str(ei.value)
        assert platform in msg and "pallas" in msg, msg
    # the interpreter is an explicit opt-in escape hatch everywhere
    assert resolve_lowering(interpret=True,
                            platform=platform) == ("mosaic", True)


def test_compile_mismatch_raises_typed_error():
    # asking a platform with no compiled path to COMPILE the kernels
    for platform in ("cpu", "gpu"):
        with pytest.raises(KernelLoweringError):
            resolve_lowering(interpret=False, lowering="mosaic",
                             platform=platform)
    # but interpret mode runs the Mosaic structure anywhere
    assert resolve_lowering(True, "mosaic", "cpu") == ("mosaic", True)
    assert resolve_lowering(True, "mosaic", "gpu") == ("mosaic", True)


@pytest.mark.parametrize("lowering", ["triton", "cuda-graphs"])
def test_bad_lowering_string_is_a_value_error(lowering):
    with pytest.raises(ValueError, match="lowering"):
        resolve_lowering(lowering=lowering, platform="tpu")
