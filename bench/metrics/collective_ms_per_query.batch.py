"""Device time of the sharded search plan's collectives per query
answered in the traced window, in ms: the summed durations, over every
chip, of the plan's all-reduce and all-gather operations in the trace,
over the window's queries.

The plan (`core/search.py`, `build_sharded_plan`) runs an all-reduce-min
of the k-th best distance and an all-reduce-max of the queries still
live in every round, one all-reduce-max before its loop, an all-reduce
sum of its counters and two all-gathers of the answers after it.  XLA
names an all-reduce after the JAX primitive that made it, so the
program compiled for a v5e 2x2 holds them as `%pmin.<n>`, `%pmax.<n>`,
`%psum.<n>` and `%all-gather.<n>`, and the TPU trace names the
operations so.  A collective's time includes its wait for the slowest
chip to reach it."""

from bench import tracing

#: the collectives as the TPU trace names them
PATTERN = r"^%(pmin|pmax|psum|all-reduce|all-gather)[.\-]"


def read(reading):
    secs, n = tracing.op_seconds(reading.trace, PATTERN)
    queries = reading.window.counters.get("queries", 0)
    if n == 0 or not queries:
        return None
    return 1e3 * secs / queries
