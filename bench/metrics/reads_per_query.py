"""Storage read: unique storage rows fetched per completed query, from the
program's `QueryStats.reads`."""


def read(run):
    return float(run.reads.sum()) / run.completed if run.completed else None
