"""Device time of every operation that is not a Pallas kernel, per sampled
hop: bucket scheduling, the window hook, the epilogue."""


def read(run):
    if run.trace is None or not run.hops or run.trace.xla_s <= 0:
        return None
    return run.trace.xla_s * 1e9 / run.hops
