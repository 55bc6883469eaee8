"""Share of the refine kernel's (query, slot) steps in the traced window
that did work, in %: the (query, leaf) pairs whose distances the kernel
computed, over queries x K x rounds, summed over the window's searches
(K leaves per query per round).  A dead slot has its copy elided and its
arithmetic skipped, but still takes its step of the grid."""

from bench import search_counts


def read(reading):
    s = search_counts.window_sums(reading)
    if not s or not s["refine_slots"]:
        return None
    return 100.0 * s["refined_pairs"] / s["refine_slots"]
