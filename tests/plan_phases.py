"""The refine rows a phased search plan runs with, from the host loop's
trail of live queries: shared by the one-device and the mesh tests."""

#: the plan's narrowest phase (`repro.core.search.PHASE_FLOOR`), kept
#: here so the schedule does not read it from the code it checks
FLOOR = 8


def phase_schedule(trail, Q):
    """The rows each round of a phased plan refines, from the live
    queries at the start of each round: a phase of B rows runs while
    more than the next phase's rows are live."""
    widths = [Q]
    while widths[-1] // 2 >= FLOOR:
        widths.append(widths[-1] // 2)
    p, rows = 0, []
    for n in trail:
        while p + 1 < len(widths) and n <= widths[p + 1]:
            p += 1
        rows.append(widths[p])
    return widths, rows
