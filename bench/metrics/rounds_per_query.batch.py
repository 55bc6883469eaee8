"""Refinement rounds per query over the traced window, from the search
plan's own counters: the sum of each search's rounds times its queries,
over the queries (each query counts the rounds of the batch it rode in,
as `rounds_per_query.serve` counts them)."""

from bench import search_counts


def read(reading):
    s = search_counts.window_sums(reading)
    if not s or not s["queries"]:
        return None
    return s["query_rounds"] / s["queries"]
