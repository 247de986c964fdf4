"""Seconds from process start to the window's start: imports, graph and
storage build, device placement, compile or cache load, warm-up rounds."""


def read(run):
    return run.setup_s
