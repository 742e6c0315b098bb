"""Share of launched walker slots that held a real walker
(``walkers_served / (walkers_served + padded_walker_slots)``)."""


def read(run):
    if run.stats is None or not run.stats.walkers_served:
        return None
    s = run.stats
    return 100.0 * s.walkers_served / (s.walkers_served + s.padded_walker_slots)
