"""Share of the (query, round) pairs of the traced window in which the
query was still live, in %: live query-rounds over queries x rounds,
summed over the window's searches.  A query is live in a round where its
next unrefined lower bound beats its k-th best distance so far; the rest
are rounds it waits for the batch's slowest query (the straggler share
is 100 less this)."""

from bench import search_counts


def read(reading):
    s = search_counts.window_sums(reading)
    if not s or not s["query_rounds"]:
        return None
    return 100.0 * s["live_query_rounds"] / s["query_rounds"]
