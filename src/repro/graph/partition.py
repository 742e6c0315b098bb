"""Contiguous vertex-range graph partitioning (paper §V-A).

The paper partitions by assigning a contiguous, equal range of vertices and
*all their neighbor lists* to one partition, because sampling requires every
edge of a vertex to be present to compute transition probabilities, and
because range membership is decidable in O(1) (``vertex // range_size``),
which the workload-aware scheduler relies on.

Device residency uses a *compact local-id* layout (DESIGN.md §8): a resident
partition's ``indptr`` covers only its own O(V/P) vertex range plus one
phantom sink row, never the full vertex space — the full ``V+1`` indptr of
the earlier layout defeated the very memory budget §V exists for.  Queue
entries keep global vertex ids (as in the paper); the rebase offset
``vertex_lo`` translates at the partition boundary.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.csr import CSRGraph


@dataclasses.dataclass(frozen=True)
class PartitionMap:
    """Cached contiguous-range bounds + O(1) partition lookup (paper §V-A).

    ``range_size = ceil(V / P)`` and ``pid(v) = min(v // range_size, P - 1)``
    — the paper's arithmetic membership test, with the bounds computed once
    and cached (they used to be recomputed on every hot-path lookup).
    """

    num_vertices: int
    num_partitions: int
    range_size: int
    bounds: np.ndarray  # (P+1,) int64 vertex range boundaries

    @staticmethod
    @functools.lru_cache(maxsize=128)
    def create(num_vertices: int, num_partitions: int) -> "PartitionMap":
        rs = -(-num_vertices // num_partitions)  # ceil
        bounds = np.minimum(
            np.arange(num_partitions + 1, dtype=np.int64) * rs, num_vertices
        )
        bounds.setflags(write=False)  # the cache shares this array
        return PartitionMap(num_vertices, num_partitions, rs, bounds)

    def pid_of(self, vertex) -> np.ndarray:
        """O(1) host-side lookup (no searchsorted, no bound rebuild)."""
        v = np.asarray(vertex)
        return np.clip(v // self.range_size, 0, self.num_partitions - 1)

    def pid_of_device(self, vertex: jax.Array) -> jax.Array:
        """Same lookup as traced device arithmetic (drain-loop scatter path)."""
        return pid_of_device(vertex, self.range_size, self.num_partitions)


def pid_of_device(vertex: jax.Array, range_size: int, num_partitions: int) -> jax.Array:
    """The membership formula as traced device arithmetic — the ONE home of
    ``min(v // range_size, P - 1)`` for jitted callers (the §V drain loop's
    cross-partition scatter and :meth:`PartitionMap.pid_of_device`)."""
    return jnp.clip(vertex // range_size, 0, num_partitions - 1).astype(jnp.int32)


def partition_of(vertex, num_vertices: int, num_partitions: int):
    """O(1) partition lookup through the cached :class:`PartitionMap`."""
    return PartitionMap.create(num_vertices, num_partitions).pid_of(vertex)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DevicePartition:
    """Device-resident compact partition CSR (local ids + phantom sink).

    ``graph`` is a local-id CSR: row ``i`` holds vertex ``vertex_lo + i``,
    and one extra *phantom* row of degree 0 at local id ``num_local_vertices``
    absorbs every neighbor outside the partition, so degree lookups on
    arbitrary (localized) ids are O(V/P)-safe without the full-V indptr.
    ``graph.indices`` therefore hold LOCAL ids; ``indices_global`` holds the
    untranslated neighbor ids, aligned edge-for-edge, for emitting walk
    output and cross-partition queue pushes in global id space.
    """

    graph: CSRGraph
    indices_global: jax.Array  # (E_P,) int32 global neighbor ids
    vertex_lo: jax.Array  # () int32 rebase offset
    vertex_hi: jax.Array  # () int32

    def tree_flatten(self):
        return (self.graph, self.indices_global, self.vertex_lo, self.vertex_hi), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def num_local_vertices(self) -> int:
        """Rows excluding the phantom sink (includes shape-padding rows)."""
        return self.graph.num_vertices - 1

    def localize(self, x: jax.Array) -> jax.Array:
        """Global vertex ids -> this partition's row-lookup ids.

        Ids outside the resident range (including -1 padding) map to the
        degree-0 phantom sink row, so any localized id is safe for
        degree/row lookups on ``graph``.  The single home of the phantom
        convention — the §V drain and the shared edge-context builder both
        route through here.
        """
        nloc = self.num_local_vertices
        inside = (x >= self.vertex_lo) & (x < self.vertex_lo + nloc)
        return jnp.where(inside, x - self.vertex_lo, nloc)


@dataclasses.dataclass
class RangePartition:
    """One partition: vertices [vertex_lo, vertex_hi) with their full rows."""

    pid: int
    vertex_lo: int
    vertex_hi: int
    # Local CSR over the owned vertex range. indptr is re-based to 0; indices
    # remain *global* vertex ids (edges may point to any partition).
    indptr: np.ndarray
    indices: np.ndarray
    weights: np.ndarray
    # Global edge offset of this partition's first edge in the source CSR —
    # lets device layouts preserve global block alignment (``edge_align``).
    edge_lo: int = 0

    @property
    def num_vertices(self) -> int:
        return self.vertex_hi - self.vertex_lo

    @property
    def num_edges(self) -> int:
        return int(self.indices.shape[0])

    def to_local_device_csr(
        self,
        pad_vertices: Optional[int] = None,
        pad_edges: Optional[int] = None,
        edge_align: int = 0,
    ) -> DevicePartition:
        """Materialize the compact O(V/P + E_P) device CSR.

        ``pad_vertices`` / ``pad_edges`` round the arrays up to a common
        shape so every partition of a graph shares ONE jit trace of the
        drain loop; padding rows have degree 0 and padding edges weight 0,
        both unreachable through masked semantics.  The one device_put of
        the host staging arrays is the DMA (async on real accelerators —
        the TransferEngine's double buffering hinges on it).

        ``edge_align`` > 0 prepends ``edge_lo % edge_align`` inert edges so
        every row keeps its *global* block offset (``start % edge_align``
        unchanged by the rebase).  The degree-bucketed pick kernels prefix-sum
        over block-aligned windows whose float association is fixed by the
        within-block position, so preserving the offset is what makes a
        partition-local pick bit-identical to the full-graph pick
        (DESIGN.md §12); the mesh-sharded walk passes the largest bucket
        segment (every smaller segment divides it).
        """
        nv = self.num_vertices
        lead = (self.edge_lo % edge_align) if edge_align > 0 else 0
        pv = max(pad_vertices or nv, nv)
        pe = max(pad_edges or (lead + self.num_edges), lead + self.num_edges)
        indptr = np.empty(pv + 2, dtype=np.int32)  # pv rows + phantom sink
        indptr[: nv + 1] = self.indptr + lead
        indptr[nv + 1 :] = self.indptr[-1] + lead
        u_loc = self.indices.astype(np.int64) - self.vertex_lo
        in_part = (u_loc >= 0) & (u_loc < nv)
        indices_local = np.where(in_part, u_loc, pv).astype(np.int32)
        epad = pe - self.num_edges - lead
        indices_local = np.pad(indices_local, (lead, epad), constant_values=pv)
        indices_global = np.pad(
            self.indices.astype(np.int32), (lead, epad), constant_values=-1
        )
        weights = np.pad(self.weights.astype(np.float32), (lead, epad))
        ip_d, il_d, ig_d, w_d = jax.device_put((indptr, indices_local, indices_global, weights))
        return DevicePartition(
            graph=CSRGraph(indptr=ip_d, indices=il_d, weights=w_d),
            indices_global=ig_d,
            vertex_lo=jnp.int32(self.vertex_lo),
            vertex_hi=jnp.int32(self.vertex_hi),
        )


def select_hubs(
    indptr: np.ndarray,
    hub_bytes: int,
    seg_big: int,
    min_degree: int = 2,
    bytes_per_edge: int = 28,
) -> np.ndarray:
    """Pick the top-degree *hub* rows that fit a per-device byte budget.

    C-SAW's transfer-bound argument (and ThunderRW's access analysis) says
    hub vertices absorb most transition traffic on power-law graphs, so
    replicating the hot few rows on every device converts most exchange hops
    into local hops.  Rows are taken greedily by descending degree (stable
    on ties, so the set is deterministic) until the cumulative replicated
    footprint exceeds ``hub_bytes``; each hub costs
    ``(degree + seg_big) * bytes_per_edge`` — the ``seg_big`` addend is the
    worst-case alignment lead :func:`hub_edge_layout` may insert, and
    ``bytes_per_edge`` covers every per-edge lane the drain replicates
    (local/global indices, weight, bias, ITS table, alias table, target
    degree: 7 × 4 bytes).  Degree-``< min_degree`` rows are never worth
    replicating (a degree-1 hop exchanges as cheaply as it resolves).

    Returns the hub vertex ids **sorted ascending** — the traced
    :func:`localize_hybrid` lookup binary-searches this array.
    """
    deg = np.diff(np.asarray(indptr)).astype(np.int64)
    if hub_bytes <= 0:
        return np.empty(0, dtype=np.int64)
    order = np.argsort(-deg, kind="stable")
    cost = np.cumsum((deg[order] + max(seg_big, 0)) * bytes_per_edge)
    take = int(np.searchsorted(cost, hub_bytes, side="right"))
    hubs = order[:take]
    hubs = hubs[deg[hubs] >= min_degree]
    return np.sort(hubs).astype(np.int64)


def hub_edge_layout(
    indptr: np.ndarray, hubs: np.ndarray, hub_region_lo: int, seg_big: int
) -> tuple:
    """Alignment-preserving placement of replicated hub rows' edges.

    Hub ``s``'s edges are copied into the device edge arrays at
    ``starts[s]``, chosen so ``starts[s] % seg_big == indptr[hubs[s]] %
    seg_big`` — the same global-block-offset invariant
    :meth:`RangePartition.to_local_device_csr` keeps for resident rows,
    which is what makes a replicated-row pick bit-identical to the
    full-graph pick (DESIGN.md §12).  Placement is sequential from
    ``hub_region_lo`` with at most ``seg_big - 1`` junk edges between
    consecutive hubs.  All inputs are device-independent, so every device
    computes the identical layout.  Returns ``(starts, end)`` with
    ``starts`` int64 ``(H,)`` and ``end`` the first unused edge slot.
    """
    hubs = np.asarray(hubs)
    starts = np.empty(hubs.shape[0], dtype=np.int64)
    cur = int(hub_region_lo)
    for s, h in enumerate(hubs):
        g = int(indptr[h])
        lead = (g - cur) % seg_big if seg_big > 0 else 0
        starts[s] = cur + lead
        cur = int(starts[s]) + int(indptr[h + 1] - indptr[h])
    return starts, cur


def hybrid_host_csr(
    part: RangePartition,
    pad_vertices: int,
    pad_edges: int,
    edge_align: int,
    hubs: np.ndarray,
    hub_starts: np.ndarray,
    indptr_full: np.ndarray,
    indices_full: np.ndarray,
    weights_full: np.ndarray,
) -> tuple:
    """Host staging arrays for the hub-replicated *hybrid* layout.

    Row space (``pv = pad_vertices`` rows of resident range, ``H`` hubs)::

        rows 0 .. pv-1        resident local rows (padding rows degree 0)
        row  pv               bridge junk row (never addressed)
        row  pv + 1 + 2s      hub s  (indptr -> hub_starts[s], degree of hub)
        row  pv + 2 + 2s      junk gap row between hub s and hub s+1
        row  pv + 2H          phantom sink (degree 0) — ``H == 0`` reduces
                              to the exact legacy compact layout

    so ``indptr`` has ``pv + 2H + 2`` entries and any id produced by
    :func:`localize_hybrid` is safe for degree/row lookups.  Junk rows hold
    whatever offsets fall between placed regions; they are unreachable
    because :func:`localize_hybrid` never returns them.  Edge arrays are
    the legacy local region (global block alignment preserved via
    ``edge_align``) followed by the replicated hub region at
    ``hub_starts`` (from :func:`hub_edge_layout`); gaps carry local-index
    ``phantom``, global-index ``-1`` and weight ``0``.

    Returns ``(indptr, indices_local, indices_global, weights)`` as numpy
    arrays, ready for per-edge lane placement + one ``device_put``.
    """
    nv = part.num_vertices
    lead = (part.edge_lo % edge_align) if edge_align > 0 else 0
    pv = max(pad_vertices, nv)
    num_hubs = int(np.asarray(hubs).shape[0])
    phantom = pv + 2 * num_hubs
    end_local = lead + part.num_edges
    pe = max(pad_edges, end_local)
    if num_hubs:
        pe = max(pe, int(hub_starts[-1]) + int(np.diff(indptr_full)[hubs[-1]]))

    indptr = np.empty(phantom + 2, dtype=np.int32)
    indptr[: nv + 1] = part.indptr + lead
    indptr[nv + 1 : pv + 1] = end_local
    cur = end_local
    for s in range(num_hubs):
        h = int(hubs[s])
        indptr[pv + 1 + 2 * s] = int(hub_starts[s])
        cur = int(hub_starts[s]) + int(indptr_full[h + 1] - indptr_full[h])
        indptr[pv + 2 + 2 * s] = cur
    indptr[phantom] = cur
    indptr[phantom + 1] = cur

    indices_local = np.full(pe, phantom, dtype=np.int32)
    indices_global = np.full(pe, -1, dtype=np.int32)
    weights = np.zeros(pe, dtype=np.float32)
    u_loc = part.indices.astype(np.int64) - part.vertex_lo
    in_part = (u_loc >= 0) & (u_loc < nv)
    indices_local[lead:end_local] = np.where(in_part, u_loc, phantom).astype(np.int32)
    indices_global[lead:end_local] = part.indices.astype(np.int32)
    weights[lead:end_local] = part.weights.astype(np.float32)
    for s in range(num_hubs):
        h = int(hubs[s])
        g0, g1 = int(indptr_full[h]), int(indptr_full[h + 1])
        d0 = int(hub_starts[s])
        hub_u = indices_full[g0:g1].astype(np.int64)
        hu_loc = hub_u - part.vertex_lo
        h_in = (hu_loc >= 0) & (hu_loc < nv)
        indices_local[d0 : d0 + g1 - g0] = np.where(h_in, hu_loc, phantom).astype(
            np.int32
        )
        indices_global[d0 : d0 + g1 - g0] = indices_full[g0:g1].astype(np.int32)
        weights[d0 : d0 + g1 - g0] = weights_full[g0:g1].astype(np.float32)
    return indptr, indices_local, indices_global, weights


def place_hub_edges(
    base: np.ndarray,
    full: np.ndarray,
    indptr_full: np.ndarray,
    hubs: np.ndarray,
    hub_starts: np.ndarray,
) -> np.ndarray:
    """Copy a full-graph per-edge lane into the hybrid layout's hub region.

    ``base`` already holds the lane's local region (and gap fill); each hub
    row's slice of ``full`` lands at its :func:`hub_edge_layout` offset.
    Used for the bias / ITS / alias / target-degree lanes, which the drain
    must read identically whether a row is resident or replicated.
    """
    out = np.asarray(base).copy()
    for s in range(int(np.asarray(hubs).shape[0])):
        h = int(hubs[s])
        g0, g1 = int(indptr_full[h]), int(indptr_full[h + 1])
        d0 = int(hub_starts[s])
        out[d0 : d0 + g1 - g0] = full[g0:g1]
    return out


def localize_hybrid(
    x: jax.Array,
    vertex_lo,
    num_rows: int,
    hubs: jax.Array,
    num_hubs: int,
) -> jax.Array:
    """Global vertex ids -> hybrid row ids (resident, hub, or phantom).

    The hub-aware extension of :meth:`DevicePartition.localize`: ids in the
    resident range rebase to rows ``0..num_rows-1`` (the resident copy wins
    when a hub also happens to be resident — both copies are pick-identical
    by the alignment invariant); ids matching a replicated hub (binary
    search over the sorted ``hubs``) map to row ``num_rows + 1 + 2*pos``;
    everything else (including ``-1`` padding) maps to the degree-0
    phantom sink at ``num_rows + 2*num_hubs``.  ``locrow != phantom`` is
    the drain's stay-local test: hub-destined walkers never enter the
    exchange — the locality win the hybrid partition exists for.
    """
    phantom = num_rows + 2 * num_hubs
    inside = (x >= vertex_lo) & (x < vertex_lo + num_rows)
    loc = jnp.where(inside, x - vertex_lo, phantom).astype(jnp.int32)
    if num_hubs:
        pos = jnp.searchsorted(hubs, x)
        posc = jnp.clip(pos, 0, num_hubs - 1)
        is_hub = (pos < num_hubs) & (hubs[posc] == x)
        hub_row = (num_rows + 1 + 2 * posc).astype(jnp.int32)
        loc = jnp.where(inside, loc, jnp.where(is_hub, hub_row, phantom)).astype(
            jnp.int32
        )
    return loc


def partition_by_vertex_range(graph: CSRGraph, num_partitions: int) -> List[RangePartition]:
    """Split a CSRGraph into ``num_partitions`` contiguous vertex ranges."""
    indptr = np.asarray(graph.indptr)
    indices = np.asarray(graph.indices)
    weights = np.asarray(graph.weights)
    n = indptr.shape[0] - 1
    bounds = PartitionMap.create(n, num_partitions).bounds
    parts: List[RangePartition] = []
    for pid in range(num_partitions):
        lo, hi = int(bounds[pid]), int(bounds[pid + 1])
        e_lo, e_hi = int(indptr[lo]), int(indptr[hi])
        local_indptr = (indptr[lo : hi + 1] - indptr[lo]).astype(np.int32)
        parts.append(
            RangePartition(
                pid=pid,
                vertex_lo=lo,
                vertex_hi=hi,
                indptr=local_indptr,
                indices=indices[e_lo:e_hi].copy(),
                weights=weights[e_lo:e_hi].copy(),
                edge_lo=e_lo,
            )
        )
    return parts
