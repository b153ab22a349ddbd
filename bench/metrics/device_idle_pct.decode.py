"""Device idle share of the decode stream window: 1 - the union of device
operation intervals over the traced window, %."""


def read(run):
    return 100.0 * run.trace.idle_share
