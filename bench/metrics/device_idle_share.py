"""Device: 1 - (union of device op intervals / traced window), mean over the
cell's devices."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share
