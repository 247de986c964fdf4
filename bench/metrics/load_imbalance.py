"""Admission and routing: max over mean of the queries each processor
executed in the window, from the program's per-round per-processor counts."""


def read(run):
    per_proc = run.per_proc.sum(axis=0)
    return float(per_proc.max() / per_proc.mean()) if per_proc.size and per_proc.mean() > 0 else None
