"""Device time of the Pallas kernels (``tpu_custom_call``) per sampled hop."""


def read(run):
    if run.trace is None or not run.hops or run.trace.pallas_s <= 0:
        return None
    return run.trace.pallas_s * 1e9 / run.hops
