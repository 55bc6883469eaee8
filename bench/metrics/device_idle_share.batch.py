"""Share of the traced window in which no operation ran on the chip:
1 - (union of the device operations' intervals) / window, in %.  On
several chips the union is each chip's, averaged over the chips
(`tracing.busy_s`): the idle share of a mean chip."""


def read(reading):
    from bench import tracing
    return 100.0 * tracing.idle_share(reading.trace)
