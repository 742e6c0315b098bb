"""Names for the sampler's work on the profiler's clock.

``scope`` names device work: every op traced inside it carries
``csaw.<name>`` in its HLO ``op_name`` metadata, so a device trace can be
split by the program's own layers.  It costs nothing at run time.
``span`` names host work: a ``csaw.<name>`` event in the profiler's host
trace, on the same clock as the device's ops; with no profiler running it
is a no-op check.  Neither is switched: tracing is off when no profiler
runs.
"""
import jax

PREFIX = "csaw."


def scope(name: str):
    return jax.named_scope(PREFIX + name)


def span(name: str):
    return jax.profiler.TraceAnnotation(PREFIX + name)
