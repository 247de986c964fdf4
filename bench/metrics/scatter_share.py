"""Chain loop and frontier expansion: device time in scatter ops over device
busy time, from the trace, mean over the cell's devices."""

OPCODES = ("scatter",)


def read(run):
    share = None if run.trace is None else run.trace.share(OPCODES)
    return None if share is None else 100.0 * share
