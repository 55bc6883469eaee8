"""A test fixture's metric: queries answered in the traced window."""


def read(reading):
    return float(reading.window.queries.shape[0])
