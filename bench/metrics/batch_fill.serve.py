"""Share of the engine's dispatched batch slots that held a real query
over the window, in %: completed / (completed + padded slots), from
the engine's counters (`QueryEngine.stats()`)."""


def read(reading):
    c = reading.window.counters
    if not c.get("completed"):
        return None
    return 100.0 * c["completed"] / (c["completed"] + c["padded_slots"])
