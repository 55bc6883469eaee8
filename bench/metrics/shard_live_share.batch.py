"""Share of the sharded search plan's (shard, round) pairs in the traced
window in which the shard refined at least one (query, leaf) pair, in
%: the plan's `shard_live_rounds` (summed over the shards) over shards x
rounds, summed over the window's searches.  The plan's rounds run in
lock step over the shards, so the rest is time a shard spends on rounds
with nothing of its own to refine.  On one device it reads 100.

Records are matched to the window's `fresh.search` spans by order, as
`bench/search_counts.py` matches them.  None where there is nothing to
read: the spans or records it needs are missing, or a record holds no
such count (a program that does not count it)."""

from bench import search_counts


def read(reading):
    try:
        from repro import obs
    except ImportError:
        return None
    shard_live_rounds = getattr(obs, "shard_live_rounds", None)
    if shard_live_rounds is None:
        return None
    lo, hi = reading.trace.window
    n = sum(1 for name, start, _ in reading.trace.host
            if name == search_counts.SEARCH_SPAN and lo <= start < hi)
    recs = obs.records(last=n) if n else []
    if not recs or len(recs) < n:
        return None
    worked = slots = 0
    for rec in recs:
        c, w = obs.counts(rec), shard_live_rounds(rec)
        if c is None or w is None:
            return None
        worked += w
        slots += rec.shards * c[0]
    return 100.0 * worked / slots if slots else None
