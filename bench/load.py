"""The one traffic generator: reads a mix file from ``bench/traffic/``.

Two kinds of mix:

- ``"closed"`` (a corpus job): one launch in flight, each blocked on.  Its
  start vertices are consecutive slices of a seeded permutation of the
  non-isolated vertices, wrapping round, as a job that covers every vertex r
  times would take them.  Keys: ``program``, ``depth``, ``walkers``.
- ``"open"`` (independent users): ``rate`` queries per second for the whole
  window, each ``walkers`` walkers of ``depth`` steps from one query vertex.
  The count is ``round(rate * seconds)`` and the arrival times are sorted
  uniform draws over the window — a Poisson process given its count — so
  every seed offers the same number of queries.  Query vertices follow
  Zipf(``zipf``) over a seeded permutation of the non-isolated vertices.
  Keys: ``program``, ``depth``, ``walkers``, ``rate``, ``zipf``.

``program`` names a sampling program and its parameters (``deepwalk``;
``node2vec`` with ``p``, ``q``; ``restart`` with ``alpha``).  Every mix also
holds ``correct``, the limit of each number the comparison reports, and
``control``, the guarantee the control law breaks (``control.py``).
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

KINDS = ("closed", "open")


def load_mix(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    if mix.get("kind") not in KINDS:
        raise ValueError(f"{path}: kind must be one of {KINDS}, got {mix.get('kind')!r}")
    for key in ("program", "depth", "walkers", "compare", "correct", "control"):
        if key not in mix:
            raise ValueError(f"{path}: missing {key!r}")
    if mix["kind"] == "open":
        for key in ("rate", "zipf"):
            if key not in mix:
                raise ValueError(f"{path}: missing {key!r}")
    return mix


def closed_starts(live_order: np.ndarray, walkers: int, launch: int) -> np.ndarray:
    """Start vertices of launch number ``launch`` (0-based)."""
    n = live_order.shape[0]
    at = (launch * walkers + np.arange(walkers)) % n
    return live_order[at]


def open_arrivals(mix: dict, seconds: float, live_order: np.ndarray,
                  rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    """(due times in seconds from the window's start, query vertices)."""
    count = int(round(float(mix["rate"]) * seconds))
    due = np.sort(rng.random(count)) * seconds
    ranks = zipf_ranks(live_order.shape[0], float(mix["zipf"]), count, rng)
    return due, live_order[ranks]


def zipf_ranks(n: int, s: float, count: int, rng: np.random.Generator) -> np.ndarray:
    """``count`` 0-based ranks in [0, n), P(rank r) ∝ (r + 1) ** -s."""
    cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64) ** -s)
    cdf /= cdf[-1]
    return np.minimum(np.searchsorted(cdf, rng.random(count), side="right"), n - 1)
