"""Refinement rounds per served query over the window, from the
engine's counters (`QueryEngine.stats()["rounds_per_query"]`): each
query counts the rounds of the batch it rode in."""


def read(reading):
    c = reading.window.counters
    if not c.get("completed"):
        return None
    return c["rounds_sum"] / c["completed"]
