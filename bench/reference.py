"""Plain reference: the walk laws on the host, and the comparison that
decides ``correct``.

Nothing here imports the program.  A walk law gives, for a walker at ``v``
that came from ``t`` (``-1`` on the first step) and started at ``home``, the
distribution of its next vertex:

- ``deepwalk``: uniform over N(v);
- ``node2vec(p, q)``: ∝ w(v, x) · (1/p if x = t, 1 if x ∈ N(t), 1/q else),
  and ∝ w(v, x) on the first step (Grover & Leskovec 2016);
- ``restart(alpha)``: ``home`` with probability alpha, else uniform over N(v).

The reference draws one next vertex from the exact law for every hop the
program took, in the same state, with numpy's generator (node2vec by
rejection against ``max(w) · max(1, 1/p, 1/q)``, which is exact).  Features
of the program's hop and of the reference's hop are compared in pairs: under
the law each difference has mean 0 given the past, so
``z = sum(d) / sqrt(sum(d^2))`` is about standard normal.  A program whose
law differs moves some ``z`` far from 0.  Hops that are not edges (or
restarts) and rows that are cut short or start at the wrong vertex are
counted exactly.

``control`` laws break one stated guarantee each; put in the program's place
they have to come out not correct (``bench/control.py``).
"""
from __future__ import annotations

import dataclasses

import numpy as np

#: the compared features, in the order ``compare`` reports them
FEATURES = ("position", "weight", "back", "near", "home", "log_degree")

#: the first ``HUB_TRUNCATION`` entries of a row are all the hub-truncating
#: control ever draws from
HUB_TRUNCATION = 512


class HostGraph:
    """CSR rows on the host, each entry keyed ``src * n + dst`` (int64), keys
    sorted.

    ``HostGraph(indptr, indices, weights)`` holds every row.  With ``held``
    (vertices, sorted and distinct) it holds only theirs: ``indices`` and
    ``weights`` are those rows, one after another, while ``indptr`` is still
    the whole graph's offsets and ``max_weight`` its largest weight.  For
    walks whose vertices are all held (``row_vertices``), ``find``,
    ``compare`` and the laws' draws then give the same numbers as on the
    whole graph.  Positions (``find``'s, ``row_start``) index the held
    entries.
    """

    def __init__(self, indptr: np.ndarray, indices: np.ndarray, weights: np.ndarray, *,
                 held: np.ndarray | None = None, max_weight: float | None = None):
        indptr = np.asarray(indptr, np.int64)
        self.indices = np.asarray(indices, np.int32)
        self.weights = np.asarray(weights, np.float32)
        self.n = indptr.shape[0] - 1
        self.deg = np.diff(indptr)
        #: the held vertices, sorted (None: every vertex)
        self.held = held
        if held is None:
            rows, self.start = np.arange(self.n, dtype=np.int64), indptr
        else:
            rows = self.held = np.asarray(held, np.int64)
            if rows.size and not (np.all(rows[1:] > rows[:-1])
                                  and rows[0] >= 0 and rows[-1] < self.n):
                raise ValueError("held vertices must be sorted, distinct and in range")
            self.start = np.concatenate([[0], np.cumsum(self.deg[rows])])
        if self.start[-1] != self.indices.size or self.weights.size != self.indices.size:
            raise ValueError("the held rows' degrees do not add up to their entries")
        self.keys = np.repeat(rows * self.n, self.deg[rows])
        self.keys += self.indices
        if self.keys.size and not (
            np.all(self.keys[1:] > self.keys[:-1])
            and self.indices.min() >= 0 and self.indices.max() < self.n
        ):
            raise ValueError("not a CSR with sorted, distinct, in-range rows")
        if max_weight is None:
            max_weight = float(self.weights.max()) if self.weights.size else 1.0
        self.max_weight = float(max_weight)

    def row_start(self, v: np.ndarray) -> np.ndarray:
        """Where each row ``v`` starts among the held entries."""
        if self.held is None:
            return self.start[v]
        slot = np.minimum(np.searchsorted(self.held, v), max(self.held.size - 1, 0))
        if not np.all(self.held[slot] == v):
            raise KeyError("a row the comparison reads is not held")
        return self.start[slot]

    def find(self, v: np.ndarray, x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(x ∈ N(v), position of the entry where it is)."""
        v = np.asarray(v, np.int64)
        x = np.asarray(x, np.int64)
        q = v * self.n + x
        pos = np.minimum(np.searchsorted(self.keys, q), max(self.keys.size - 1, 0))
        hit = (v >= 0) & (x >= 0) & (self.keys[pos] == q)
        return hit, pos


def row_vertices(walks: np.ndarray, n: int) -> np.ndarray:
    """The vertices whose rows ``compare`` reads for ``walks``: every vertex
    in them, sorted, once."""
    w = np.asarray(walks, np.int64).ravel()
    return np.unique(w[(w >= 0) & (w < n)])


@dataclasses.dataclass(frozen=True)
class Law:
    """A walk law: ``name`` in deepwalk / node2vec / restart, its parameters,
    and ``broken``, the guarantee a control drops (None for the sound law)."""

    name: str
    p: float = 1.0
    q: float = 1.0
    alpha: float = 0.0
    broken: str | None = None

    @classmethod
    def of(cls, program: dict, broken: str | None = None) -> "Law":
        name = program["name"]
        if name == "deepwalk":
            return cls("deepwalk", broken=broken)
        if name == "node2vec":
            return cls("node2vec", p=float(program["p"]), q=float(program["q"]), broken=broken)
        if name == "restart":
            return cls("restart", alpha=float(program["alpha"]), broken=broken)
        raise ValueError(f"no reference law for program {name!r}")

    def draw(self, g: HostGraph, t, v, home, rng: np.random.Generator) -> np.ndarray:
        """One next vertex per walker from state (t, v, home); v must be live."""
        v = np.asarray(v, np.int64)
        deg = g.deg[v]
        start = g.row_start(v)
        if self.name == "restart":
            width = deg
            nxt = g.indices[start + rng.integers(0, width)].astype(np.int64)
            if self.broken != "restart":
                nxt = np.where(rng.random(v.shape) < self.alpha, home, nxt)
            return nxt
        if self.name == "deepwalk":
            width = np.minimum(deg, HUB_TRUNCATION) if self.broken == "hub_rows" else deg
            return g.indices[start + rng.integers(0, width)].astype(np.int64)
        # node2vec, by rejection: propose uniformly, accept ∝ w · factor
        t = np.asarray(t, np.int64)
        ceiling = g.max_weight * max(1.0, 1.0 / self.p, 1.0 / self.q)
        out = np.full(v.shape, -1, np.int64)
        todo = np.arange(v.shape[0])
        while todo.size:
            e = start[todo] + rng.integers(0, deg[todo])
            x = g.indices[e].astype(np.int64)
            tt = t[todo]
            if self.broken == "membership":
                near = np.zeros(todo.shape, bool)
            else:
                near = g.find(tt, x)[0]
            factor = np.where(x == tt, 1.0 / self.p, np.where(near, 1.0, 1.0 / self.q))
            factor = np.where(tt < 0, 1.0, factor)
            ok = rng.random(todo.shape) * ceiling < g.weights[e] * factor
            out[todo[ok]] = x[ok]
            todo = todo[~ok]
        return out


def walks_from_law(g: HostGraph, law: Law, starts: np.ndarray, depth: int,
                   rng: np.random.Generator) -> np.ndarray:
    """Whole walks drawn from ``law`` (the control in the program's place)."""
    starts = np.asarray(starts, np.int64)
    out = np.empty((starts.shape[0], depth + 1), np.int64)
    out[:, 0] = starts
    prev = np.full(starts.shape, -1, np.int64)
    for k in range(depth):
        out[:, k + 1] = law.draw(g, prev, out[:, k], starts, rng)
        prev = out[:, k]
    return out


def _features(g: HostGraph, t, v, home, x) -> np.ndarray:
    """(len(FEATURES), hops) features of next vertex x from state (t, v)."""
    on_edge, pos = g.find(v, x)
    deg_v = np.maximum(g.deg[v], 1)
    position = np.where(on_edge, (pos - g.row_start(v) + 0.5) / deg_v, 0.5)
    weight = np.where(on_edge, g.weights[pos], 0.0)
    back = (x == t).astype(np.float64)
    near = g.find(t, x)[0].astype(np.float64)
    at_home = (x == home).astype(np.float64)
    log_degree = np.log1p(g.deg[np.maximum(x, 0)])
    return np.stack([position, weight, back, near, at_home, log_degree])


def compare(g: HostGraph, law: Law, walks: np.ndarray, starts: np.ndarray,
            rng: np.random.Generator) -> dict:
    """Judge ``walks`` (rows of ``depth + 1`` vertices from ``starts``) against
    the sound form of ``law``.

    Returns the compared numbers: ``bad_rows`` (wrong start or cut short),
    ``bad_hops`` (a hop that is neither an edge nor, for restart laws, a
    restart home), ``max_abs_z`` (largest |z| over ``FEATURES``), ``hops``
    (how many were compared) and ``z`` (each feature's z).
    """
    sound = dataclasses.replace(law, broken=None)
    walks = np.asarray(walks, np.int64)
    starts = np.asarray(starts, np.int64)
    bad_rows = int(np.sum((walks[:, 0] != starts) | np.any(walks < 0, axis=1)))
    depth = walks.shape[1] - 1
    prev = np.concatenate([np.full((walks.shape[0], 1), -1, np.int64), walks[:, :-1]], axis=1)
    t = prev[:, :depth].ravel()
    v = walks[:, :depth].ravel()
    x = walks[:, 1:].ravel()
    home = np.repeat(starts, depth)
    live = (v >= 0) & (x >= 0)
    outside = live & ((v >= g.n) | (x >= g.n))
    live &= ~outside
    t, v, x, home = t[live], v[live], x[live], home[live]
    on_edge = g.find(v, x)[0]
    legal = on_edge | ((x == home) if law.name == "restart" else False)
    bad_hops = int(np.sum(~legal) + np.sum(outside))
    # judge the law on legal hops from live rows only: a bad hop is already
    # a failure, and the reference cannot draw from a state off the graph
    keep = legal & (g.deg[v] > 0)
    t, v, x, home = t[keep], v[keep], x[keep], home[keep]
    y = sound.draw(g, t, v, home, rng)
    d = _features(g, t, v, home, x) - _features(g, t, v, home, y)
    num = d.sum(axis=1)
    den = np.sqrt((d * d).sum(axis=1))
    z = np.where(den > 0, num / np.where(den > 0, den, 1.0), 0.0)
    return {
        "bad_rows": bad_rows,
        "bad_hops": bad_hops,
        "max_abs_z": float(np.max(np.abs(z))) if z.size else 0.0,
        "hops": int(x.size),
        "z": {f: float(zi) for f, zi in zip(FEATURES, z)},
    }


def verdict(numbers: dict, limits: dict) -> tuple[bool, list[tuple[str, float, float]]]:
    """(every number within its limit, [(name, number, limit), ...])."""
    rows = [(name, float(numbers[name]), float(limit)) for name, limit in limits.items()]
    return all(value <= limit for _, value, limit in rows), rows
