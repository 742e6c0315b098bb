"""Median time a query waited in the service before its launch began
(``RequestLatency.queue_ms``: submission to launch start)."""

import numpy as np


def read(run):
    if not run.latencies:
        return None
    return float(np.median([lat.queue_ms for lat in run.latencies]))
