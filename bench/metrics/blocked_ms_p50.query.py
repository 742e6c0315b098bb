"""Median time a query, once due, waited for the launch in flight
(``RequestLatency.blocked_ms``: from its cohort falling due, or from its own
arrival if it joined a cohort already due, to its launch start);
``queue_ms`` less this is the batching policy's own wait."""

import numpy as np


def read(run):
    blocked = [getattr(lat, "blocked_ms", None) for lat in run.latencies or ()]
    if not blocked or None in blocked:
        return None
    return float(np.median(blocked))
