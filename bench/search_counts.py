"""The search plan's own counts for the searches of a traced window.

`FreshIndex.search` marks each call with a host span named
`fresh.search` and appends one record to a ring the process keeps
(`repro.obs`): the call's queries, its leaves per round (K), and the
plan's counters (rounds, live query-rounds, refined (query, leaf)
pairs).  The reduced trace keeps no span arguments, so records are
matched to spans by order: the n spans that start inside the window are
the ring's n newest records, since nothing searches between the window
and the reading of the metrics.

`window_sums` is None where there is nothing to read: a program without
the ring or the spans, no span in the window, fewer records than spans,
or a call whose plan kept no counts.
"""

from __future__ import annotations

from typing import Dict, Optional

#: the facade's span around one search
SEARCH_SPAN = "fresh.search"


def window_sums(reading) -> Optional[Dict[str, int]]:
    """Sums over the window's searches: queries; query-rounds (queries
    x rounds); live query-rounds; refined pairs; refine slots (queries x
    K x rounds)."""
    try:
        from repro import obs
    except ImportError:
        return None
    lo, hi = reading.trace.window
    n = sum(1 for name, start, _ in reading.trace.host
            if name == SEARCH_SPAN and lo <= start < hi)
    if n == 0:
        return None
    recs = obs.records(last=n)
    if len(recs) < n:
        return None
    out = {"queries": 0, "query_rounds": 0, "live_query_rounds": 0,
           "refined_pairs": 0, "refine_slots": 0}
    for rec in recs:
        c = obs.counts(rec)
        if c is None:
            return None
        rounds, live, refined = c
        out["queries"] += rec.queries
        out["query_rounds"] += rec.queries * rounds
        out["live_query_rounds"] += live
        out["refined_pairs"] += refined
        out["refine_slots"] += rec.queries * rec.round_leaves * rounds
    return out
