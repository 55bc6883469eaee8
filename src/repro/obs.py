"""What `FreshIndex` records about its own calls.

Every search through the facade appends one `Record` to a bounded ring
that the whole process shares (an index freed after its searches leaves
its records behind): how many queries the call held, the leaves a round
takes per query, the shards the index is split over, and the search
plan's counter array (`repro.core.search.COUNTERS`: rounds, live
query-rounds, refined (query, leaf) pairs, the rows the refine call ran
with summed over rounds, and the rounds in which a shard refined
anything), left on the device as the plan returned it.  The search path
makes no device-to-host transfer for this; a count is copied to the
host only when `counts`, `kernel_rows`, `shard_live_rounds` or `totals`
reads it.

A sharded index (a mesh of S devices) records the same counters:
`rounds` are the rounds of the collective loop, the same on every
shard; a query is live in a round where it is live on any shard; the
refined pairs, kernel rows and shard live rounds are summed over the
shards.  One device is the case S = 1.

The facade also marks its calls in a profiler trace: `fresh.search`
spans a search, and `fresh.search.prepare` the host work before the
plan is dispatched (query conversion, stop rule, knobs, the search
view); `fresh.build` spans `FreshIndex.build` and `fresh.shard`
`FreshIndex.shard`.  Search records and spans come in the same order,
one each per call that returns.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Any, List, Optional, Tuple

#: records the ring keeps; older ones are dropped
CAPACITY = 1024

#: the facade's trace spans
SEARCH_SPAN = "fresh.search"
PREPARE_SPAN = "fresh.search.prepare"
BUILD_SPAN = "fresh.build"
SHARD_SPAN = "fresh.shard"


@dataclasses.dataclass(frozen=True)
class Record:
    queries: int                 # rows of the call's query batch
    round_leaves: int            # leaves a round takes per query (K)
    counts: Any                  # (5,) int32 device array, or None
    shards: int = 1              # devices the searched index is split over


_ring: collections.deque = collections.deque(maxlen=CAPACITY)
_lock = threading.Lock()


def record(queries: int, round_leaves: int, counts,
           shards: int = 1) -> None:
    """Append one call's record (the oldest falls out when full)."""
    with _lock:
        _ring.append(Record(int(queries), int(round_leaves), counts,
                            int(shards)))


def records(last: Optional[int] = None) -> List[Record]:
    """The ring's records, oldest first; the `last` newest only when
    given (all of them where it holds fewer)."""
    with _lock:
        out = list(_ring)
    return out if last is None else out[len(out) - min(last, len(out)):]


def clear() -> None:
    with _lock:
        _ring.clear()


def counts(rec: Record) -> Optional[Tuple[int, int, int]]:
    """(rounds, live query-rounds, refined pairs) of one record, copied
    to the host; None where the call was not counted."""
    if rec.counts is None:
        return None
    r, live, refined = (int(v) for v in rec.counts.tolist()[:3])
    return r, live, refined


def kernel_rows(rec: Record) -> Optional[int]:
    """The rows the record's refine calls ran with, summed over its
    rounds (the refine grid's steps over K), copied to the host; None
    where the call was not counted."""
    return None if rec.counts is None else int(rec.counts.tolist()[3])


def shard_live_rounds(rec: Record) -> Optional[int]:
    """The rounds in which a shard refined at least one (query, leaf)
    pair, summed over the record's shards (at most shards x rounds; one
    device: its rounds), copied to the host; None where the call did not
    count them."""
    if rec.counts is None:
        return None
    c = rec.counts.tolist()
    return int(c[4]) if len(c) > 4 else None


def totals() -> dict:
    """Sums over the records the ring holds (the last `CAPACITY`
    searches): searches, queries, rounds (summed per search), live
    query-rounds and refined (query, leaf) pairs, and `uncounted`, the
    searches among them whose plan kept no counts (their queries are in
    `queries`, nothing of theirs in the three counts)."""
    out = {"searches": 0, "queries": 0, "rounds": 0,
           "live_query_rounds": 0, "refined_pairs": 0, "uncounted": 0}
    for rec in records():
        out["searches"] += 1
        out["queries"] += rec.queries
        c = counts(rec)
        if c is None:
            out["uncounted"] += 1
            continue
        out["rounds"] += c[0]
        out["live_query_rounds"] += c[1]
        out["refined_pairs"] += c[2]
    return out
