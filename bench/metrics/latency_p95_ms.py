"""95th percentile latency over every query completed in the window, in ms
(all queries, not a percentile of rounds)."""

import numpy as np


def read(run):
    return float(np.percentile(run.latencies_s, 95)) * 1e3 if run.latencies_s.size else None
