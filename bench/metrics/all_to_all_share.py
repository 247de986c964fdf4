"""Storage read across chips: device time in all-to-all ops over device busy
time, from the trace, mean over the cell's devices."""

OPCODES = ("all-to-all",)


def read(run):
    share = None if run.trace is None else run.trace.share(OPCODES)
    return None if share is None else 100.0 * share
