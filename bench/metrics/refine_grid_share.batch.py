"""Share of the refine kernel's grid steps in the traced window that did
work, in %: the (query, leaf) pairs whose distances the kernel computed,
over the rows the refine calls ran with times K, summed over the window's
searches.  The search plan counts the rows (`kernel_rows`, the fourth of
its counters); where it refines each round over every row of the batch,
this reads as `refine_slot_share.batch`, and where it narrows the batch
as queries finish, the grid loses the finished queries' steps.

Records are matched to the window's `fresh.search` spans by order, as
`bench/search_counts.py` matches them.  None where there is nothing to
read: the spans or records it needs are missing, or a call's record holds
no row count (a program that does not count them)."""

from bench import search_counts


def read(reading):
    try:
        from repro import obs
    except ImportError:
        return None
    kernel_rows = getattr(obs, "kernel_rows", None)
    if kernel_rows is None:
        return None
    lo, hi = reading.trace.window
    n = sum(1 for name, start, _ in reading.trace.host
            if name == search_counts.SEARCH_SPAN and lo <= start < hi)
    recs = obs.records(last=n) if n else []
    if not recs or len(recs) < n:
        return None
    refined = steps = 0
    for rec in recs:
        c, rows = obs.counts(rec), kernel_rows(rec)
        if c is None or rows is None:
            return None
        refined += c[2]
        steps += rows * rec.round_leaves
    return 100.0 * refined / steps if steps else None
