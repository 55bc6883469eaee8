"""Share of the traced window in which no operation ran on the chip:
1 - (union of the device operations' intervals) / window, in %."""


def read(reading):
    from bench import tracing
    return 100.0 * tracing.idle_share(reading.trace)
