"""FreSh core: the paper's contribution (lock-free data series index).

The supported public surface is the `FreshIndex` facade::

    from repro.api import FreshIndex, IndexConfig
    index = FreshIndex.build(series, IndexConfig(leaf_capacity=64))
    dist, ids = index.search(queries, k=10)      # exact k-NN
    index.add(batch); index.compact()            # incremental updates
    index.shard(mesh)                            # multi-device
    index.save(d); FreshIndex.load(d)            # checkpoint

The free functions re-exported below (`build_index`, `search`,
`search_bruteforce`, `shard_index`, `make_sharded_search`) are the engine
underneath the facade.  They remain importable as thin compatibility shims
for existing call sites — see the migration table in `repro.api` — but new
code should go through `FreshIndex`, which threads one `IndexConfig`
through every stage instead of hand-copied kwargs.

Host control plane (faithful to the paper's shared-memory algorithms):
    traverse   — traverse-object ADT (Section III)
    refresh    — Refresh lock-free transformation (Section IV, Alg. 2-3)
    tree       — fat-leaf lock-free iSAX tree (Section V-B1)
    baselines  — conventional lock-free baselines (Section VI)

Device data plane (TPU-native adaptation — see DESIGN.md §2):
    isax       — PAA / iSAX / distance math
    index      — flat bucketed index build (BC + TP stages)
    builder    — IndexBuilder: the Refresh-driven phase pipeline behind
                 FreshIndex.build (streaming feed, lock-free multi-worker
                 builds, incremental compaction via merge_sorted_delta)
    search     — exact k-NN pruning + refinement (PS + RS stages)
    dtw        — DTW similarity (Section II generality claim): banded DTW
                 + LB_Keogh envelope bound + exact DTW 1-NN search
"""

from . import isax  # noqa: F401
from .builder import IndexBuilder, merge_sorted_delta  # noqa: F401
from .dtw import lb_keogh, dtw_band, search_dtw  # noqa: F401
from .index import (FlatIndex, build_index, build_index_host,  # noqa: F401
                    build_index_sharded, index_stats, leaf_stats_blocks,
                    pad_leaves)
from .refresh import (CounterObject, Injectors, RefreshExecutor,  # noqa: F401
                      RefreshRun, WorkerCrash)
from .search import (build_sharded_plan, build_sharded_search,  # noqa: F401
                     make_sharded_search, merge_delta_topk,
                     prepare_queries, run_search, search,
                     search_bruteforce, search_plan, shard_index,
                     snapshot_search)
from .traverse import (ArrayTraverse, Executor, SequentialExecutor,  # noqa: F401
                       StageStats, TraverseObject,
                       check_traversing_property, traverse_complete)
