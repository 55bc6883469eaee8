"""Device time of the refine kernel (`kernels/refine.py`) per query
answered in the traced window, in ms: the summed durations of the
kernel's events in the trace over the window's queries."""

from bench import tracing

#: the kernel's operations as the TPU trace names them
PATTERN = r"^%refine_topk\."


def read(reading):
    secs, n = tracing.op_seconds(reading.trace, PATTERN)
    queries = reading.window.counters.get("queries", 0)
    if n == 0 or not queries:
        return None
    return 1e3 * secs / queries
