"""The main path's Pallas kernels compile for a TPU v5e, at real widths.

Nothing here runs on a chip: each test lowers a kernel against a
described (not attached) v5e and compiles it with the TPU compiler that
ships with jax, which refuses what Mosaic would refuse on the chip —
illegal block shapes, unsupported reshapes, too much VMEM — none of which
interpret mode can see.  Shapes only: an index of 2^22 series of length
256 in leaves of 64, 16 PAA segments, 8 leaves per refine round, query
batches of 1 and 128, k of 10 and 100, series stored in float32 and
bfloat16.

Each compiled program must hold a Mosaic kernel (`tpu_custom_call`).
The topology is described inside a fixture, never while a module is
imported, and the persistent compilation cache is off around the
compiles (such a compile can be written to the cache but not read back
without a chip).
"""

import os

import jax
import jax.numpy as jnp
import pytest

N_SERIES = 1 << 22
L = 256
M = 64            # leaf capacity
W = 16            # PAA segments
K = 8             # leaves per refine round
NL = N_SERIES // M


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:    # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """spec(shape, dtype) -> a ShapeDtypeStruct on one described v5e."""
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compile_tpu(spec, no_compile_cache):
    """compile_tpu(fn, *shapes) -> HLO text of fn compiled for the v5e,
    asserted to hold a Mosaic kernel."""
    def run(fn, *args):
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
        return text
    return run


@pytest.fixture
def on_tpu(monkeypatch):
    """refine_topk resolves its lowering from jax.default_backend(), which
    is the CPU here: steer it to the TPU's (compile Mosaic)."""
    from repro.kernels import _compat, refine

    def resolve(interpret=None, lowering=None):
        return _compat.resolve_lowering(interpret, lowering, platform="tpu")
    monkeypatch.setattr(refine, "resolve_lowering", resolve)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("dma_depth", [1, 2], ids=["pipelined", "dma_ring"])
@pytest.mark.parametrize("Q,k", [(1, 10), (128, 100)])
def test_refine_compiles(compile_tpu, spec, on_tpu, Q, k, dma_depth, dtype):
    from repro.kernels.ops import refine_topk

    def fn(q, q_sq, series, sq_norms, ids, alive, bsf_d, bsf_e):
        return refine_topk(q, q_sq, series, sq_norms, ids, alive, bsf_d,
                           bsf_e, leaf_capacity=M, k=k, dma_depth=dma_depth)
    compile_tpu(fn, spec((Q, L)), spec((Q,)), spec((N_SERIES, L), dtype),
                spec((N_SERIES,)), spec((Q, K), jnp.int32),
                spec((Q, K), jnp.bool_), spec((Q, k)),
                spec((Q, k), jnp.int32))


@pytest.mark.parametrize("znorm", [True, False], ids=["znorm", "raw"])
def test_summarize_compiles(compile_tpu, spec, znorm):
    from repro.kernels.isax_summarize import summarize
    compile_tpu(lambda x: summarize(x, segments=W, bits=8, znorm=znorm,
                                    interpret=False),
                spec((N_SERIES, L)))


@pytest.mark.parametrize("Q", [1, 128])
def test_lb_distance_compiles(compile_tpu, spec, Q):
    from repro.kernels.lb_distance import lb_distance
    compile_tpu(lambda q, lo, hi: lb_distance(q, lo, hi, series_len=L,
                                              interpret=False),
                spec((Q, W)), spec((NL, W)), spec((NL, W)))


def test_ed_argmin_compiles(compile_tpu, spec):
    from repro.kernels.ed_argmin import ed_argmin
    compile_tpu(lambda q, xs: ed_argmin(q, xs, interpret=False),
                spec((128, L)), spec((4096, L)))



def _metric_pattern(name):
    """The op-name pattern by which a benchmark metric reads a kernel's
    device time (`bench/metrics/<name>.py`)."""
    import importlib.util
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    path = os.path.join(root, "bench", "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.PATTERN


@pytest.mark.parametrize("dma_depth", [1, 2], ids=["pipelined", "dma_ring"])
def test_search_plan_names_its_kernels(compile_tpu, spec, on_tpu,
                                       monkeypatch, dma_depth):
    """The search plan compiled for the v5e holds instructions that the
    benchmark's kernel patterns match, for either refine structure.  The
    kernels' own jit wrappers are taken away, so the names come from the
    kernels themselves and not from a Python function around them.  The
    128-row plan refines in phases of 128, 64, 32, 16 and 8 rows: one
    refine call each, and the refine pattern matches every one."""
    import re

    from repro.core.index import FlatIndex
    from repro.core.search import search_plan
    from repro.kernels import ops
    from repro.kernels.lb_distance import lb_distance
    from repro.kernels.refine import refine_topk

    monkeypatch.setattr(ops, "resolve_interpret",
                        lambda interpret=None: False)
    monkeypatch.setattr(ops, "_refine_topk", refine_topk.__wrapped__)
    monkeypatch.setattr(ops, "_lb_distance", lb_distance.__wrapped__)
    idx = FlatIndex(series=spec((N_SERIES, L)), paa=spec((N_SERIES, W)),
                    words=spec((N_SERIES, W), jnp.uint8),
                    sq_norms=spec((N_SERIES,)),
                    perm=spec((N_SERIES,), jnp.int32),
                    valid=spec((N_SERIES,), jnp.bool_),
                    leaf_lo=spec((NL, W)), leaf_hi=spec((NL, W)),
                    leaf_valid=spec((NL,), jnp.bool_))
    text = compile_tpu(
        lambda i, q: search_plan(i, q, k=10, round_leaves=K,
                                 backend="pallas", dma_depth=dma_depth),
        idx, spec((128, L)))
    kernels = [ln.strip().removeprefix("ROOT ")
               for ln in text.splitlines() if "tpu_custom_call" in ln]
    for metric in ("refine_ms_per_query.batch",
                   "lb_distance_roofline.batch"):
        rx = re.compile(_metric_pattern(metric))
        assert any(rx.search(n) for n in kernels), (metric, kernels)
    rx = re.compile(_metric_pattern("refine_ms_per_query.batch"))
    refine = [n for n in kernels if rx.search(n)]
    assert len(kernels) == len(refine) + 1, kernels     # and lb_distance
    # a refine call's first output is the (rows, 1, lanes) distance buffer
    widths = [int(re.search(r"= \(f32\[(\d+),1,", n).group(1))
              for n in refine]
    assert sorted(widths) == [8, 16, 32, 64, 128], widths
    # the leaf norms are laid out as the kernel reads them once, before
    # the rounds: no round relayouts them
    norms = f"f32[{NL},1,{M}]"
    in_rounds = [ln for ln in text.splitlines() if norms in ln
                 and " reshape(" in ln and "while/body" in ln]
    assert not in_rounds, in_rounds


# --------------------------------------------------------------------- #
# four chips: the rw256-16m-4chip deployment, 2^22 series a chip
# --------------------------------------------------------------------- #
N_MESH = 4 * N_SERIES
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def mesh4(topo):
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.array(topo.devices), ("data",))


@pytest.fixture
def kernels_on_tpu(monkeypatch, on_tpu):
    """The kernels' interpret default resolves from the CPU backend here:
    steer every kernel the build and the plan call to Mosaic."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "resolve_interpret",
                        lambda interpret=None: False)


def _placed(mesh4, shape, dtype=jnp.float32):
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = P("data", None) if len(shape) == 2 else P("data")
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh4, spec))


def _device_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


def test_mesh_build_compiles(mesh4, no_compile_cache, kernels_on_tpu):
    """The build over series row-sharded over four v5e: the summarize
    kernel runs per chip (an automatically partitioned Mosaic kernel is
    refused), and a chip's series, index and temporaries fit its HBM."""
    from repro.core.index import build_index_sharded
    compiled = jax.jit(lambda x: build_index_sharded(
        x, mesh4, "data", segments=W, leaf_capacity=M, backend="pallas")
    ).lower(_placed(mesh4, (N_MESH, L))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES * 0.7


def test_sharded_plan_names_its_kernels_and_collectives(
        mesh4, no_compile_cache, kernels_on_tpu, monkeypatch):
    """The sharded plan over 2^22 series a chip compiles for four v5e
    with the Mosaic kernels; the benchmark's kernel and collective
    patterns match what it holds, and the leaf norms are laid out once
    before the rounds.  The 128-row plan refines in phases of 128, 64,
    32, 16 and 8 rows: one refine call each, and in each phase's loop
    the round's all-reduce-min and -max over that many rows."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.index import FlatIndex
    from repro.core.search import build_sharded_plan
    from repro.kernels import ops
    from repro.kernels.lb_distance import lb_distance
    from repro.kernels.refine import refine_topk

    monkeypatch.setattr(ops, "_refine_topk", refine_topk.__wrapped__)
    monkeypatch.setattr(ops, "_lb_distance", lb_distance.__wrapped__)
    nl = N_MESH // M

    def s(shape, dtype=jnp.float32):
        return _placed(mesh4, shape, dtype)
    idx = FlatIndex(series=s((N_MESH, L)), paa=s((N_MESH, W)),
                    words=s((N_MESH, W), jnp.uint8), sq_norms=s((N_MESH,)),
                    perm=s((N_MESH,), jnp.int32),
                    valid=s((N_MESH,), jnp.bool_),
                    leaf_lo=s((nl, W)), leaf_hi=s((nl, W)),
                    leaf_valid=s((nl,), jnp.bool_))
    q = jax.ShapeDtypeStruct((128, L), jnp.float32,
                             sharding=NamedSharding(mesh4, P()))
    plan = build_sharded_plan(mesh4, k=10, round_leaves=K, backend="pallas")
    compiled = jax.jit(plan).lower(idx, q).compile()
    text = compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES * 0.4
    kernels = [ln.strip().removeprefix("ROOT ")
               for ln in text.splitlines() if "tpu_custom_call" in ln]
    for metric in ("refine_ms_per_query.batch",
                   "lb_distance_roofline.batch"):
        rx = re.compile(_metric_pattern(metric))
        assert any(rx.search(n) for n in kernels), (metric, kernels)
    rx = re.compile(_metric_pattern("refine_ms_per_query.batch"))
    refine = [n for n in kernels if rx.search(n)]
    assert len(kernels) == len(refine) + 1, kernels     # and lb_distance
    # a refine call's first output is the (rows, 1, lanes) distance buffer
    widths = [int(re.search(r"= \(f32\[(\d+),1,", n).group(1))
              for n in refine]
    assert sorted(widths) == [8, 16, 32, 64, 128], widths
    # every collective the compiler kept is one the reader counts, and
    # nothing else is but the scalar combiners inside the all-reduces
    # (no operation of their own): the round's all-reduce-min and -max
    # are there
    rx = re.compile(_metric_pattern("collective_ms_per_query.batch"))
    ops_of = re.findall(r"^\s*(?:ROOT )?(%\S+) = (\S+) ([a-z\-]+)\(",
                        text, re.M)
    collective = {n for n, _, op in ops_of
                  if op.startswith(("all-reduce", "all-gather",
                                    "reduce-scatter", "collective-permute",
                                    "all-to-all"))}
    counted = {n for n, _, _ in ops_of if rx.search(n)}
    assert collective and collective <= counted, (collective, counted)
    combiners = {n for n, ty, _ in ops_of
                 if n in counted - collective and "[]" in ty}
    assert counted - collective == combiners, counted - collective
    kinds = {n.split(".")[0] for n in collective}
    assert {"%pmin", "%pmax", "%all-gather"} <= kinds, kinds
    # a phase's round all-reduces a vector of its rows
    rows_of = {kind: {int(m) for n, ty, _ in ops_of
                      if n.startswith(kind + ".")
                      for m in re.findall(r"^[fs]32\[(\d+)\]", ty)}
               for kind in ("%pmin", "%pmax")}
    assert rows_of["%pmin"] == {8, 16, 32, 64, 128}, rows_of
    assert rows_of["%pmax"] == {8, 16, 32, 64, 128}, rows_of
    # the leaf norms are laid out as the kernel reads them once, before
    # the rounds: no round relayouts them
    norms = f"f32[{nl // 4},1,{M}]"
    in_rounds = [ln for ln in text.splitlines() if norms in ln
                 and " reshape(" in ln and "while/body" in ln]
    assert not in_rounds, in_rounds
