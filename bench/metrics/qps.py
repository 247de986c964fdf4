"""Completed queries per second of the window (host clock, client side)."""


def read(run):
    return run.completed / run.window_s if run.window_s > 0 else None
