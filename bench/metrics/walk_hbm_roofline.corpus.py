"""The least time the traced hops need at the chip's HBM bandwidth, over the
device's busy time.

Each hop moves at least 16 bytes whatever implements it: the two row offsets
of the current vertex, the sampled neighbour id, and the walk entry written.
Over all busy time (Pallas and XLA alike), it cannot pass 100%.
"""

BYTES_PER_HOP = 16


def read(run):
    if run.trace is None or not run.hops or run.trace.busy_s <= 0:
        return None
    least_s = run.hops * BYTES_PER_HOP / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / run.trace.busy_s
