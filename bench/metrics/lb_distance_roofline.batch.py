"""Share of its roofline that the prefix-bound kernel
(`kernels/lb_distance.py`) reached in the traced window, in %.

The least time of one search's bound stage, for Q queries over NL
leaves of w segments, is the larger of bytes / HBM bandwidth and
flops / peak, counting only what any implementation of the stage must
do:

  bytes  read leaf_lo and leaf_hi, (NL, w) float32 each, and the
         queries' PAAs, (Q, w) float32, once
  flops  4 per (query, leaf, segment): the two differences from the
         region's edges, the square and the sum (the max against 0 is
         not counted)

The (Q, NL) bound matrix that today's kernel writes is not counted: a
fused prune-and-select need not write it.  The bytes bound it: at
Q = 128, w = 16, NL = 65,536 they take 10.2 us at 819 GB/s, the flops
2.7 us at 197 TFLOP/s.  The share is that least time times the searches
in the window, over the summed device time of the kernel's events.
"""

from bench import tracing

#: the kernel's operations as the TPU trace names them
PATTERN = r"^%lb_distance\."


def least_seconds(q: int, n_leaves: int, w: int, peaks: dict) -> float:
    nbytes = 4.0 * (2 * n_leaves * w + q * w)
    flops = 4.0 * q * n_leaves * w
    return max(nbytes / peaks["hbm_bytes_per_s"],
               flops / peaks["flops_per_s"])


def read(reading):
    secs, n = tracing.op_seconds(reading.trace, PATTERN)
    calls = reading.window.counters.get("calls", 0)
    if n == 0 or secs <= 0 or not calls:
        return None
    s = reading.shapes
    least = least_seconds(int(reading.cell.mix["batch"]), s["n_leaves"],
                          s["segments"], reading.peaks())
    return 100.0 * calls * least / secs
