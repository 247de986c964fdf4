"""Median latency over every query completed in the window, in ms: from when
its client sent it to the end of the call that returned its answer."""

import numpy as np


def read(run):
    return float(np.percentile(run.latencies_s, 50)) * 1e3 if run.latencies_s.size else None
