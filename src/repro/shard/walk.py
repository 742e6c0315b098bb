"""Owner-routed sharded random walk over a device mesh (paper §V-D, scaled).

Each device of the mesh holds ONE contiguous vertex-range partition as a
compact local-id CSR (HBM ∝ 1/D, ``graph.partition.DevicePartition``) — plus
a small replicated *hub* region (below) — and a device-resident frontier
queue of the walkers currently AT its vertices (``shard.exchange.ShardQueue``).
A drain round:

1. flushes the deferred emigrants: per-destination cumsum compaction into
   fixed ``(D, slots)`` buffers, ONE tiled ``all_to_all``, per-destination
   overflow *deferred* to the next round (never dropped), received walkers
   pushed into the local queue,
2. runs ``sub_rounds`` local sub-rounds, each popping the queue (every
   popped walker's vertex is resident or hub-replicated, so its full
   neighbor row is local), taking one walk step through the SAME
   degree-bucketed selection dispatch the single-device engines use
   (``core.backend``; both backends), and pushing survivors back into the
   local queue (resident- or hub-destined) or the deferred buffer
   (cold-row emigrants),
3. a fused ``psum`` of live/deferred counts decides termination and skips
   empty exchanges.

**Hub replication (C-SAW's transfer-bound argument, DESIGN.md §14).**  On
power-law graphs a few hub rows absorb most transition traffic, so the
top-degree rows — budgeted in bytes, ``graph.partition.select_hubs`` — are
replicated on EVERY device alongside the compact range shard
(``hybrid_host_csr``).  A hop into a hub resolves locally on whatever device
the walker already occupies and never enters the exchange; only cold-row
hops pay the collective.  Hub edges keep their global block alignment
(``hub_edge_layout``), so a pick off a replicated row is bit-identical to
the owner's pick.  Interleaving the exchange of round N's emigrants with
round N+1's local sub-rounds (step 1 vs 2 above) amortizes one collective
over ``sub_rounds`` local steps.

The whole drain is one ``lax.scan`` inside one ``shard_map`` inside one
``jit`` per (shard shape, spec, backend) — meshes of the same shape reuse
the trace; a host loop re-invokes the compiled block only while walkers
remain (deferred-overflow slack).

**Bit-identical parity.**  ``sharded_random_walk`` reproduces single-device
``engine.random_walk`` exactly, bit for bit, on both backends, for EVERY
non-opaque transition program — flat and window biases (including
``needs_deg_u``), identity / teleport / MH-accept epilogues — because every
source of divergence is pinned (DESIGN.md §12, §14):

- *RNG*: the engine draws each step's uniforms as position-indexed ``(W,)``
  vectors under ``fold_in(key, depth)`` chains.  The sharded drain derives
  the SAME counted stream per entry — keyed by the walker's own (depth,
  instance), not by its slot on whatever device it landed on — via
  ``draw(key_of(depth))[instance]``.  Counted RNG is also what makes the
  sub-round restructure safe: a draw depends on (depth, instance), never on
  WHEN or WHERE the entry was popped.
- *Selection arithmetic*: the pick kernels prefix-sum block-aligned CSR windows
  whose float association is fixed by within-window position, so partitions
  are materialized with ``edge_align = max(buckets)`` lead padding —
  every row (resident AND hub-replicated) keeps its global ``start % seg``
  offset and the partition-local prefix sum reproduces the full-graph bits.
- *Flat biases*: evaluated ONCE on the full graph at partition time and
  sliced per shard (a neighbor-degree bias needs non-resident degrees, which
  a shard cannot see), so per-edge bias bits match by construction.
- *Prev-dependent window biases* (node2vec): the previous vertex's neighbor
  row is CARRIED with the walker through the exchange (gathered at the
  source shard, which owns it), so ``is_prev_neighbor`` is exact without
  any replicated adjacency beyond the hub region — a hub's row is on every
  shard, so a hub ``prev`` is searched there and never carried, and the
  carried width is the widest non-hub row.
- *Non-resident degrees* (``needs_deg_u`` window biases, MH-accept): a
  replicated per-edge *target-degree lane* ``deg_tgt[e] = deg(indices[e])``
  — sliced/placed exactly like the flat bias — resolves ``deg(u)`` for any
  candidate at the source shard, no degree ever crosses the wire.
  MH-accept locates the selected neighbor's edge by binary search in the
  current row (rows are destination-sorted, ``csr_from_edges``) and decides
  acceptance through the engine's own ``transition.mh_stay``.

Only programs with OPAQUE hooks (``OpaqueBias`` / ``OpaqueEpilogue`` —
arbitrary user callables that may read any non-resident state) fall back to
:func:`replicated_psum_walk` (correct, collective-heavy, not parity-exact).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.api import EdgeCtx, SamplingSpec
from repro.core import backend as bk
from repro.core import methods as mt
from repro.core import select as sel
from repro.core import transition as tp
from repro.core.engine import (
    WalkResult,
    _degree,
    _edge_ctx,
    _is_prev_neighbor_window,
    _walker_rows,
    flat_method_plan,
    window_envelope,
)
from repro.distributed.sharding import shard_map_unchecked
from repro.graph.csr import CSRGraph
from repro.graph.partition import (
    PartitionMap,
    hub_edge_layout,
    hybrid_host_csr,
    localize_hybrid,
    partition_by_vertex_range,
    pid_of_device,
    place_hub_edges,
    select_hubs,
)
from repro.shard import exchange as ex

#: safety valve on the host drain loop (each block makes guaranteed progress
#: as long as exchange_slots >= 1, so this is never hit by a sane config)
_MAX_BLOCKS = 4096


def _per_entry(base_key, d, inst, valid, draw):
    """Per-entry counted RNG: ``draw(fold_in(base_key, d_e))[inst_e]``.

    ``draw(key) -> (W,)`` must reproduce one of the engine's per-step
    position-indexed vectors; indexing it at the walker's instance id makes
    the draw placement-independent.  The common case — every live entry in
    the batch at the same depth (no deferral backlog) — computes ONE ``(W,)``
    vector and gathers; mixed-depth batches pay a vmapped per-entry draw.
    """
    i = jnp.maximum(inst, 0)
    d0 = d[0]
    same = jnp.all(~valid | (d == d0))

    def cheap(_):
        return draw(jax.random.fold_in(base_key, d0))[i]

    def general(_):
        return jax.vmap(lambda dd, j: draw(jax.random.fold_in(base_key, dd))[j])(d, i)

    return jax.lax.cond(same, cheap, general, None)


def _carried_window_bias(graph, program, v, prev, d, curq, prow, deg_tgt,
                         ids, prev_row, prev_hub, steps):
    """The window-bias hook closed over carried walker state.

    Mirrors ``engine._window_bias_fn`` except for where prev-neighbor
    membership looks: a hub ``prev`` (``prev_hub``) has its full row on
    every device, so the engine's binary search runs over that local row
    (``prev_row`` in the hybrid row space, global ids ``ids``, ``steps``
    halvings); any other ``prev`` is compared against the CARRIED ``(B,
    prow_w)`` neighbor row (``-2``-padded, gathered at the source shard,
    whose width is the widest NON-hub row).  Identical booleans either way,
    no replicated adjacency beyond the hub region.  ``needs_deg_u`` hooks
    gather the replicated per-edge target-degree lane at the window's edge
    positions (``eidx``) — the same integers the engine's ``_degree(graph,
    u)`` row lookup produces, since ``deg_tgt[e] = deg(indices[e])`` on the
    full graph by construction.
    """
    wb = program.bias
    deg_v = _degree(graph, curq)
    e_hi = deg_tgt.shape[0] - 1

    def bias_of(u, w, mask, eidx=None, rows=None):
        if wb.needs_deg_u:
            du = jnp.where(mask, deg_tgt[jnp.clip(eidx, 0, e_hi)], 0)
        else:  # declared unused — skip the window-wide lane gather
            du = jnp.zeros(u.shape, jnp.int32)
        v_, prev_, d_, deg_v_ = _walker_rows(rows, v, prev, d, deg_v)
        ipn = None
        if wb.needs_prev_neighbors:
            prow_, prev_row_, prev_hub_ = _walker_rows(rows, prow, prev_row, prev_hub)
            carried = jnp.any(u[..., :, None] == prow_[..., None, :], axis=-1)
            searched = _is_prev_neighbor_window(
                graph.indptr, ids, prev_row_, prev_, u, mask, steps=steps
            )
            ipn = (
                jnp.where(prev_hub_[..., None], searched, carried)
                & mask
                & (prev_ >= 0)[..., None]
                & (u >= 0)
            )
        ctx = EdgeCtx(
            v=v_, u=u, weight=w, deg_v=deg_v_,
            deg_u=du, prev=prev_,
            is_prev_neighbor=ipn, depth=d_[..., None],
        )
        return wb.fn(ctx)

    return bias_of


def _is_hub(x, hubs, num_hubs: int):
    """Whether each global id in ``x`` is a replicated hub (sorted ``hubs``)."""
    if not num_hubs:
        return jnp.zeros(x.shape, bool)
    pos = jnp.clip(jnp.searchsorted(hubs, x), 0, num_hubs - 1)
    return (hubs[pos] == x) & (x >= 0)


def _selected_deg(iglob, deg_tgt, st, dg, u, steps):
    """deg(u) of the SELECTED neighbor via the replicated degree lane.

    The pick kernels return the selected vertex id, not its edge position,
    so locate ``u`` by binary search in the current row's global-id slice
    ``iglob[st : st + dg]`` — destination-sorted by ``csr_from_edges``, an
    ordering both the resident and the hub-replicated copy preserve — and
    read ``deg_tgt`` there.  Parallel duplicate edges share a target (and
    therefore a degree), so any match position is correct.  ``steps`` must
    satisfy ``2**steps >= max row degree``; dead walkers (``u < 0``) read a
    harmless 1 (masked downstream).
    """
    e_hi = iglob.shape[0] - 1
    lo = jnp.zeros_like(dg)
    hi = dg

    def body(_, lohi):
        lo, hi = lohi
        open_ = lo < hi
        mid = (lo + hi) // 2
        val = iglob[jnp.clip(st + mid, 0, e_hi)]
        go_right = val < u
        lo = jnp.where(open_ & go_right, mid + 1, lo)
        hi = jnp.where(open_ & ~go_right, mid, hi)
        return lo, hi

    lo, _ = jax.lax.fori_loop(0, steps, body, (lo, hi))
    pos = jnp.clip(st + lo, 0, e_hi)
    found = (lo < dg) & (iglob[pos] == u) & (u >= 0)
    return jnp.where(found, deg_tgt[jnp.clip(pos, 0, deg_tgt.shape[0] - 1)], 1)


# ---------------------------------------------------------------------------
# The compiled drain block (one jit per config; cached)
# ---------------------------------------------------------------------------

_DRAIN_CACHE: dict = {}
#: bound on cached drain traces — like every jit-static-spec entry point in
#: this repo (engine.random_walk, oom._drain), a FRESHLY CONSTRUCTED spec is
#: a new trace key (its hooks are new closures), so callers should reuse
#: spec objects across calls; the bound turns a caller that doesn't into
#: steady-state recompiles instead of an unbounded cache leak
_DRAIN_CACHE_MAX = 64


def _drain_block(
    mesh: Mesh, axis: str, *, spec: SamplingSpec, be: str, num_devices: int,
    num_inst: int, depth: int, cap: int, slots: int, prow_w: int,
    buckets: tuple, use_chunked: bool, rounds: int, range_size: int,
    num_hubs: int, sub_rounds: int, search_steps: int, methods: tuple = (),
):
    """Build (or fetch) the jitted shard_map drain for one static config."""
    cfg = (mesh, axis, spec, be, num_devices, num_inst, depth, cap, slots,
           prow_w, buckets, use_chunked, rounds, range_size, num_hubs,
           sub_rounds, search_steps, methods)
    if cfg in _DRAIN_CACHE:
        return _DRAIN_CACHE[cfg]
    while len(_DRAIN_CACHE) >= _DRAIN_CACHE_MAX:
        _DRAIN_CACHE.pop(next(iter(_DRAIN_CACHE)))

    program = tp.lower(spec)
    mode = program.mode
    needs_prev = prow_w > 0
    nfields = 5 if needs_prev else 4
    num_dest = num_devices
    use_mh = isinstance(program.epilogue, tp.MHAcceptEpilogue)
    phantom = range_size + 2 * num_hubs

    use_alias = any(m == "alias" for m in methods)
    use_rej = any(m == "rejection" for m in methods)

    def body(indptr, iloc, iglob, wts, bias, vlo, prob, alias, rowmax,
             deg_tgt, hubs, qfields, qcount, qdropped, dfields, dcount,
             walks, key, seeds, limits, wmax):
        indptr, iloc, iglob, wts, bias, vlo0 = (
            indptr[0], iloc[0], iglob[0], wts[0], bias[0], vlo[0]
        )
        # partition-local slices of the full-graph method tables; None'd out
        # when the plan never reads them, exactly like the engine's pytree
        tbl = mt.MethodTables(
            prob=prob[0] if use_alias else None,
            alias=alias[0] if use_alias else None,
            row_max=rowmax[0] if use_rej else None,
        )
        deg_tgt0 = deg_tgt[0]
        qfields = tuple(f[0] for f in qfields)
        dfields = tuple(f[0] for f in dfields)
        qcount, qdropped, dcount = qcount[0], qdropped[0], dcount[0]
        local = CSRGraph(indptr=indptr, indices=iloc, weights=wts)
        padded = bk.pad_walk_csr(iglob, bias, buckets)

        def rowid(x):
            return localize_hybrid(x, vlo0, range_size, hubs, num_hubs)

        def sub_step(carry, _):
            q, defer, walks, stats = carry
            # throttle the pop so (deferred + newly stepped) fits one batch
            entries, taken, q = ex.queue_pop(q, cap, limit=cap - defer.count)
            v, inst, d = entries[0], entries[1], entries[2]
            prev = entries[3]
            prow = entries[4] if needs_prev else None
            valid = inst >= 0
            curq = jnp.where(valid, rowid(v), -1)

            # -- one walk step, on the engine's exact counted RNG stream ----
            def u_draw(kd):  # fold_in(kstep, 1) -> fold_in(·, 0): bucket pick
                return jax.random.uniform(
                    jax.random.fold_in(jax.random.fold_in(kd, 1), 0),
                    (num_inst,), dtype=jnp.float32)

            def tail_draw(kd):  # fold_in(kstep, 1) -> fold_in(·, 1): tail
                return jax.random.uniform(
                    jax.random.fold_in(jax.random.fold_in(kd, 1), 1),
                    (num_inst,), dtype=jnp.float32)

            r0 = _per_entry(key, d, inst, valid, u_draw)
            tail = _per_entry(key, d, inst, valid, tail_draw) if use_chunked else None

            def rej_col(c):  # fold_in(kstep,1) -> fold_in(·,2) -> c
                def rej_draw(kd):
                    return jax.random.uniform(
                        jax.random.fold_in(jax.random.fold_in(
                            jax.random.fold_in(kd, 1), 2), c),
                        (num_inst,), dtype=jnp.float32)
                return _per_entry(key, d, inst, valid, rej_draw)

            def rej_budget(iters):  # the engine's rejection_randoms layout
                cols = [rej_col(c) for c in range(2 * iters)]
                return jnp.stack(cols, axis=-1).reshape(cols[0].shape + (iters, 2))
            if mode == "flat" and methods:
                # adaptive selection (DESIGN.md §13): the plan was computed
                # from the SAME full-graph bias as the in-memory engine, so
                # supplying the engine's counted streams (instance-indexed)
                # keeps the sharded walk bit-identical per method
                rej = rej_budget(sel.REJECT_ITERS) if use_rej else None
                u = bk.walk_step_adaptive(
                    key, indptr, iglob, bias, padded, curq,
                    buckets=buckets, use_chunked=use_chunked,
                    methods=methods, tables=tbl, backend=be,
                    rand=r0, tail_rand=tail, rej_rand=rej,
                )
            elif mode == "flat":
                if be == "pallas":
                    u = bk.walk_step_bucketed(
                        key, indptr, iglob, bias, padded, curq,
                        buckets=buckets, use_chunked=use_chunked,
                        rand=r0, tail_rand=tail,
                    )
                else:
                    u = bk.walk_step_flat_reference(
                        key, indptr, iglob, bias, padded, curq,
                        buckets=buckets, use_chunked=use_chunked,
                        max_degree=None, rand=r0, tail_rand=tail,
                    )
            else:
                bias_of = _carried_window_bias(
                    local, program, v, prev, d, curq, prow, deg_tgt0,
                    iglob, rowid(prev), _is_hub(prev, hubs, num_hubs), search_steps,
                )
                # the engine's rejection envelope, from the FULL graph's weights
                envelope = rej = None
                if program.bias.max_ratio is not None:
                    envelope = window_envelope(program.bias, wmax)
                    rej = rej_budget(bk.TAIL_REJECT_ITERS)
                u = bk.walk_step_bucketed_window(
                    key, indptr, iglob, wts, padded, curq, bias_of,
                    buckets=buckets, use_chunked=use_chunked, backend=be,
                    rand=r0, tail_rand=tail, envelope=envelope, rej_rand=rej,
                )

            # -- epilogue (engine's fused post-select step, instance-keyed) --
            epi = program.epilogue
            if isinstance(epi, tp.TeleportEpilogue):
                def tel_draw(kd):
                    kj, _ = jax.random.split(jax.random.fold_in(kd, 2))
                    return jax.random.uniform(kj, (num_inst,))

                teleport = _per_entry(key, d, inst, valid, tel_draw) < epi.prob
                if epi.target == "uniform":
                    def tgt_draw(kd):
                        _, kv = jax.random.split(jax.random.fold_in(kd, 2))
                        return jax.random.randint(
                            kv, (num_inst,), 0, epi.num_vertices)

                    tgt = _per_entry(key, d, inst, valid, tgt_draw)
                elif epi.target == "fixed":
                    tgt = jnp.full_like(u, epi.vertex)
                else:  # "home"
                    tgt = seeds[jnp.maximum(inst, 0)].astype(jnp.int32)
                nxt = jnp.where(teleport & (u >= 0), tgt, u)
            elif use_mh:
                # MH-accept, owner-routed: deg(v) is the current row's true
                # degree (resident or hub copy — both full rows) and deg(u)
                # comes off the replicated target-degree lane; the counted
                # uniform and the acceptance arithmetic (transition.mh_stay)
                # are the engine's own, so the stay/move bit is identical
                def acc_draw(kd):
                    return jax.random.uniform(
                        jax.random.fold_in(kd, 2), (num_inst,))

                st_mh = indptr[jnp.maximum(curq, 0)]
                dg_mh = indptr[jnp.maximum(curq, 0) + 1] - st_mh
                deg_u = _selected_deg(iglob, deg_tgt0, st_mh, dg_mh, u, search_steps)
                acc = _per_entry(key, d, inst, valid, acc_draw)
                stay = tp.mh_stay(acc, dg_mh, deg_u)
                nxt = jnp.where(stay & (v >= 0) & (u >= 0), v, u)
            else:  # IdentityEpilogue (opaque rejected upstream)
                nxt = u
            nxt = jnp.where(u >= 0, nxt, -1)

            ok = valid & (nxt >= 0)
            walks = walks.at[
                jnp.where(ok, inst, num_inst), jnp.maximum(d, 0) + 1
            ].set(nxt, mode="drop")
            cont = ok & (d + 1 < limits[jnp.maximum(inst, 0)])

            # -- survivors: resident/hub stay local, cold rows defer --------
            new_entry = [nxt, inst, d + 1, v]
            if needs_prev:
                # the NEXT step's is_prev_neighbor needs N(v): gather v's
                # row here, the one shard that holds it, and carry it along
                # — unless v is a hub, whose row every shard holds
                offs = jnp.arange(prow_w, dtype=jnp.int32)
                st = indptr[jnp.maximum(curq, 0)]
                dgv = _degree(local, curq)
                carry = valid & ~_is_hub(v, hubs, num_hubs)
                rmask = (offs[None, :] < dgv[:, None]) & carry[:, None]
                new_entry.append(
                    jnp.where(rmask, iglob[jnp.where(rmask, st[:, None] + offs, 0)], -2)
                )
            stay_local = rowid(nxt) != phantom
            q = ex.queue_push(q, tuple(new_entry), cont & stay_local)
            defer = ex.queue_push(defer, tuple(new_entry), cont & ~stay_local)
            hub_hops = jnp.sum((valid & (curq > range_size)).astype(jnp.int32))
            stats = stats + jnp.stack(
                [jnp.zeros((), jnp.int32), hub_hops,
                 jnp.sum(valid.astype(jnp.int32))]
            )
            return (q, defer, walks, stats), None

        def do_round(carry, defer_live):
            q, defer, walks, stats = carry

            # -- flush deferred emigrants through ONE tiled all_to_all ------
            def exch(args):
                q, defer, stats = args
                dmask = jnp.arange(cap, dtype=jnp.int32) < defer.count
                dest = pid_of_device(defer.fields[0], range_size, num_dest)
                send, sent, leftover, left_count = ex.route_by_owner(
                    defer.fields, dest, dmask, num_dest, slots
                )
                recv = ex.all_to_all_fields(send, axis)
                rflat = tuple(
                    r.reshape((num_dest * slots,) + r.shape[2:]) for r in recv
                )
                q = ex.queue_push(q, rflat, rflat[1] >= 0)
                defer = ex.ShardQueue(
                    tuple(f[:cap] for f in leftover), left_count, defer.dropped
                )
                z = jnp.zeros((), jnp.int32)
                return q, defer, stats + jnp.stack([jnp.sum(sent), z, z])

            q, defer, stats = jax.lax.cond(
                defer_live > 0, exch, lambda a: a, (q, defer, stats)
            )
            # -- overlap: local sub-rounds drain resident + hub hops --------
            # unrolled at trace level so each sub-round is one inlined step
            carry = (q, defer, walks, stats)
            for _ in range(sub_rounds):
                carry, _ = sub_step(carry, None)
            return carry

        def round_step(carry, _):
            q, defer, walks, stats = carry
            # one fused psum: [live anywhere, deferred anywhere] — gates the
            # whole round AND lets an all-local round skip its collective
            tot = jax.lax.psum(
                jnp.stack([q.count + defer.count, defer.count]), axis
            )
            carry = jax.lax.cond(
                tot[0] > 0, lambda c: do_round(c, tot[1]), lambda c: c, carry
            )
            return carry, None

        q0 = ex.ShardQueue(qfields, qcount, qdropped)
        d0 = ex.ShardQueue(dfields, dcount, jnp.zeros((), jnp.int32))
        stats0 = jnp.zeros((3,), jnp.int32)
        (q, defer, walks, stats), _ = jax.lax.scan(
            round_step, (q0, d0, walks, stats0), None, length=rounds
        )
        live = jax.lax.psum(q.count + defer.count, axis)
        walks = jax.lax.pmax(walks, axis)
        return (
            tuple(f[None] for f in q.fields), q.count[None], q.dropped[None],
            tuple(f[None] for f in defer.fields), defer.count[None],
            walks, live, stats[None],
        )

    dshard = P(axis)
    rep = P()
    in_specs = (
        dshard, dshard, dshard, dshard, dshard, dshard,  # graph arrays
        dshard, dshard, dshard,                          # method tables
        dshard, rep,                                     # deg lane, hub ids
        (dshard,) * nfields, dshard, dshard,             # queue
        (dshard,) * nfields, dshard,                     # deferred
        rep, rep, rep, rep, rep,                # walks, key, seeds, limits, max weight
    )
    out_specs = (
        (dshard,) * nfields, dshard, dshard,
        (dshard,) * nfields, dshard,
        rep, rep, dshard,
    )
    fn = jax.jit(
        shard_map_unchecked(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    )
    _DRAIN_CACHE[cfg] = fn
    return fn


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def sharded_random_walk(
    mesh: Mesh,
    graph: CSRGraph,
    seeds,
    key: jax.Array,
    *,
    depth: int,
    spec: SamplingSpec,
    max_degree: int,
    axis: str = "data",
    backend: bk.Backend = "auto",
    depth_limits: Optional[np.ndarray] = None,
    exchange_slots: Optional[int] = None,
    queue_capacity: Optional[int] = None,
    rounds_per_block: Optional[int] = None,
    hub_bytes: Optional[int] = None,
    sub_rounds: int = 1,
) -> WalkResult:
    """Random walk over a range-sharded graph: owners step, emigrants route.

    Each device of ``mesh`` (along ``axis``) holds one vertex-range shard of
    ``graph`` — per-device CSR footprint ∝ 1/D plus the replicated hub
    region — and walkers migrate to the shard owning their frontier vertex
    only when it is neither resident nor hub-replicated.  For every
    non-opaque transition program the result is **bit-identical** to
    single-device ``engine.random_walk(graph, seeds, key, ...)`` with the
    same arguments, on both backends (the parity contract in the module
    docstring; for window programs ``max_degree`` must be the true max row
    degree, the same contract the engine's exact window bucket plan already
    imposes).  Programs with opaque hooks fall back to
    :func:`replicated_psum_walk`.

    ``depth_limits`` (optional ``(W,)``, values in ``[0, depth]``) stops
    instance ``i`` after its own number of steps — the batched service packs
    heterogeneous requests into one launch with it.  ``-1`` seeds are
    padding and emit all--1 rows.

    ``exchange_slots`` bounds the per-destination send buffer of one round;
    walkers past it are deferred to later rounds, never dropped (the queue
    itself defaults to holding the whole walker population, so ``dropped``
    stays zero).  ``rounds_per_block`` sizes the compiled scan; the host
    re-invokes the block while any shard still holds live walkers.

    ``hub_bytes`` budgets the per-device replicated hub region (default:
    roughly half a shard's edge footprint; ``0`` disables replication —
    the pure range-shard layout of the earlier design).  ``sub_rounds``
    local sub-rounds run between consecutive exchanges, so resident- and
    hub-destined walkers take several steps per collective.  Migration
    COUNT is trajectory-determined (bit-identical walks), so extra
    sub-rounds never reduce exchange volume — they amortize collective
    *latency* on real multi-chip meshes at the price of extra fixed-shape
    step launches; the default is 1, which is also what forced-host-device
    runs (no network latency to hide) should use.

    The returned :class:`~repro.core.engine.WalkResult` carries a ``stats``
    dict (exchange traffic, hub/resident hop split, layout footprint) —
    the observability the BENCH flatness gate and the hub-efficacy
    benchmarks read.
    """
    program = tp.lower(spec)
    mode = program.mode
    owner_ok = mode != "opaque" and not isinstance(
        program.epilogue, tp.OpaqueEpilogue
    )
    seeds_np = np.asarray(seeds, dtype=np.int32)
    num_inst = int(seeds_np.shape[0])
    if depth_limits is None:
        limits_np = np.full((num_inst,), depth, np.int32)
    else:
        limits_np = np.asarray(depth_limits, dtype=np.int32)
        if limits_np.shape != (num_inst,):
            raise ValueError(
                f"depth_limits shape {limits_np.shape} != ({num_inst},)"
            )
        if limits_np.size and (limits_np.min() < 0 or limits_np.max() > depth):
            raise ValueError(
                f"depth_limits must lie in [0, depth={depth}], got "
                f"[{limits_np.min()}, {limits_np.max()}]"
            )

    if not owner_ok:
        walks = replicated_psum_walk(
            mesh, graph, jnp.asarray(seeds_np), key,
            depth=depth, spec=spec, max_degree=max_degree, axis=axis,
        )
        walks = jnp.where(
            jnp.arange(depth + 1)[None, :] <= jnp.asarray(limits_np)[:, None],
            walks, -1,
        )
        lengths = jnp.sum(walks >= 0, axis=-1)
        return WalkResult(walks, lengths, jnp.sum(jnp.maximum(lengths - 1, 0)))

    if depth < 1 or num_inst == 0:
        walks = jnp.full((num_inst, depth + 1), -1, jnp.int32)
        if num_inst:
            walks = walks.at[:, 0].set(jnp.asarray(seeds_np))
        lengths = jnp.sum(walks >= 0, axis=-1)
        return WalkResult(walks, lengths, jnp.sum(jnp.maximum(lengths - 1, 0)))

    num_devices = int(mesh.shape[axis])
    be = bk.resolve_backend(backend)
    if mode == "flat":
        buckets, use_chunked = bk.walk_bucket_plan(max_degree)
    else:
        buckets, use_chunked = bk.walk_bucket_plan_window(max_degree)
    seg_big = max(buckets)
    pm = PartitionMap.create(graph.num_vertices, num_devices)
    parts = partition_by_vertex_range(graph, num_devices)
    needs_prev = mode == "window" and program.bias.needs_prev_neighbors
    use_mh = isinstance(program.epilogue, tp.MHAcceptEpilogue)
    needs_degu = mode == "window" and program.bias.needs_deg_u
    indptr_np = np.asarray(graph.indptr)
    indices_np = np.asarray(graph.indices)
    weights_np = np.asarray(graph.weights)
    deg_np = np.diff(indptr_np)
    true_max_deg = int(deg_np.max()) if indptr_np.size > 1 else 0
    # halvings of a binary search over any row (MH's selected-degree lookup,
    # the prev-membership search over hub rows)
    search_steps = min(32, max(1, true_max_deg.bit_length()))

    # -- hub selection: replicate the hot top-degree rows on every device ---
    num_edges = int(indices_np.shape[0])
    if num_devices > 1:
        if hub_bytes is None:
            # default ≈ half a shard's replicated-lane footprint: high enough
            # to catch power-law hubs, low enough to keep HBM ∝ 1/D
            hb = (4 * 7 * num_edges) // (2 * num_devices)
        else:
            hb = int(hub_bytes)
    else:
        hb = 0  # single device: everything is already resident
    hubs_np = select_hubs(indptr_np, hb, seg_big)
    num_hubs = int(hubs_np.shape[0])
    # prev rows travel with their walkers except hub rows, which every shard
    # holds: the carried width is the widest NON-hub row
    prow_w = 0
    if needs_prev:
        carried = np.ones(deg_np.shape, bool)
        carried[hubs_np] = False
        prow_w = max(int(deg_np[carried].max(initial=0)), 1)

    # -- materialize shards: common padded shape, global block alignment ----
    pad_v = pm.range_size
    pad_e_local = max((p.edge_lo % seg_big) + p.num_edges for p in parts)
    hub_lo = -(-pad_e_local // seg_big) * seg_big
    hub_starts, hub_end = hub_edge_layout(indptr_np, hubs_np, hub_lo, seg_big)
    pad_e = max(pad_e_local, hub_end)
    phantom = pad_v + 2 * num_hubs
    host_csrs = [
        hybrid_host_csr(
            p, pad_v, pad_e, seg_big, hubs_np, hub_starts,
            indptr_np, indices_np, weights_np,
        )
        for p in parts
    ]

    def _edge_lane(full):
        """Slice a full-graph per-edge lane into every shard's hybrid layout."""
        lane = np.zeros((num_devices, pad_e), full.dtype)
        for i, p in enumerate(parts):
            lead = p.edge_lo % seg_big
            lane[i, lead : lead + p.num_edges] = full[
                p.edge_lo : p.edge_lo + p.num_edges
            ]
            if num_hubs:
                lane[i] = place_hub_edges(
                    lane[i], full, indptr_np, hubs_np, hub_starts
                )
        return lane

    if mode == "flat":
        # flat biases may read non-resident state (e.g. neighbor degrees):
        # evaluate ONCE on the full graph, slice per shard — bit-equal to the
        # engine's full-graph evaluation by construction
        fb_full = np.asarray(program.bias.fn(graph), dtype=np.float32)
        bias_np = _edge_lane(fb_full)
    else:
        bias_np = np.stack([h[3] for h in host_csrs])  # edge weights

    # -- adaptive selection plan (DESIGN.md §13): planned from the SAME
    # full-graph bias as the in-memory engine (same cache entry), so the
    # method per cohort — and therefore every drawn bit — matches
    # single-device random_walk exactly.  Tables are sliced per shard the
    # way the bias is: alias redirects are row-local (row slicing preserves
    # them, hub rows are copied whole) and the lead padding keeps global
    # block alignment.
    sel_methods: tuple = ()
    tables_full = mt.EMPTY_TABLES
    if mode == "flat":
        sel_methods, tables_full = flat_method_plan(graph, program, max_degree)
        if mt.is_trivial(sel_methods):
            sel_methods = ()
    prob_np = np.zeros((num_devices, pad_e), np.float32)
    alias_np = np.zeros((num_devices, pad_e), np.int32)
    rowmax_np = np.zeros((num_devices, phantom + 1), np.float32)
    if tables_full.prob is not None:
        prob_np = _edge_lane(np.asarray(tables_full.prob))
        alias_np = _edge_lane(np.asarray(tables_full.alias))
    if tables_full.row_max is not None:
        rm_full = np.asarray(tables_full.row_max)
        for i, p in enumerate(parts):
            rowmax_np[i, : p.num_vertices] = rm_full[p.vertex_lo : p.vertex_hi]
            if num_hubs:
                rowmax_np[i, pad_v + 1 + 2 * np.arange(num_hubs)] = rm_full[hubs_np]

    # -- replicated target-degree lane: deg(u) for any candidate edge, read
    # at the SOURCE shard (needs_deg_u window hooks, MH-accept) — degrees
    # never cross the wire
    if use_mh or needs_degu:
        dt_full = np.diff(indptr_np).astype(np.int32)[indices_np]
        dt_np = _edge_lane(dt_full)
    else:
        dt_np = np.zeros((num_devices, 1), np.int32)

    shardspec = NamedSharding(mesh, P(axis))
    rep = NamedSharding(mesh, P())
    put_s = functools.partial(jax.device_put, device=shardspec)
    indptr_s = put_s(jnp.asarray(np.stack([h[0] for h in host_csrs])))
    iloc_s = put_s(jnp.asarray(np.stack([h[1] for h in host_csrs])))
    iglob_s = put_s(jnp.asarray(np.stack([h[2] for h in host_csrs])))
    wts_s = put_s(jnp.asarray(np.stack([h[3] for h in host_csrs])))
    bias_s = put_s(jnp.asarray(bias_np))
    vlo_s = put_s(jnp.asarray([p.vertex_lo for p in parts], jnp.int32))
    prob_s = put_s(jnp.asarray(prob_np))
    alias_s = put_s(jnp.asarray(alias_np))
    rowmax_s = put_s(jnp.asarray(rowmax_np))
    deg_tgt_s = put_s(jnp.asarray(dt_np))
    hubs_d = jax.device_put(
        jnp.asarray(
            hubs_np if num_hubs else np.full((1,), -1, np.int64), jnp.int32
        ),
        rep,
    )

    walks0 = np.full((num_inst, depth + 1), -1, np.int32)
    walks0[:, 0] = seeds_np

    # -- initial queues: every live seed starts at its owner ----------------
    cap = num_inst if queue_capacity is None else int(queue_capacity)
    if cap < 1:
        raise ValueError(f"queue_capacity must be >= 1, got {cap}")
    slots = cap if exchange_slots is None else int(exchange_slots)
    if slots < 1:
        raise ValueError(f"exchange_slots must be >= 1, got {slots}")
    slots = min(slots, cap)
    widths = (0, 0, 0, 0) + ((prow_w,) if needs_prev else ())
    live0 = (seeds_np >= 0) & (limits_np > 0)
    owners = pm.pid_of(np.maximum(seeds_np, 0))
    qf0 = [
        np.full((num_devices, cap) if w == 0 else (num_devices, cap, w),
                -1 if w == 0 else -2, np.int32)
        for w in widths
    ]
    qc0 = np.zeros((num_devices,), np.int32)
    for dv in range(num_devices):
        idxs = np.nonzero(live0 & (owners == dv))[0].astype(np.int32)
        k = len(idxs)
        if k > cap:
            raise ValueError(
                f"queue_capacity={cap} cannot hold the {k} seeds owned by "
                f"shard {dv}; raise queue_capacity (default: num instances)"
            )
        qf0[0][dv, :k] = seeds_np[idxs]
        qf0[1][dv, :k] = idxs
        qf0[2][dv, :k] = 0
        qf0[3][dv, :k] = -1
        qc0[dv] = k

    qfields = tuple(put_s(jnp.asarray(f)) for f in qf0)
    qcount = put_s(jnp.asarray(qc0))
    qdropped = put_s(jnp.zeros((num_devices,), jnp.int32))
    dfields = tuple(
        put_s(jnp.full((num_devices, cap) if w == 0 else (num_devices, cap, w),
                       -1 if w == 0 else -2, jnp.int32))
        for w in widths
    )
    dcount = put_s(jnp.zeros((num_devices,), jnp.int32))
    walks = jax.device_put(jnp.asarray(walks0), rep)
    seeds_d = jax.device_put(jnp.asarray(seeds_np), rep)
    limits_d = jax.device_put(jnp.asarray(limits_np), rep)
    # the window path's rejection envelope reads the full graph's max weight
    wmax_d = jax.device_put(
        jnp.asarray(weights_np.max() if weights_np.size else 0.0, jnp.float32), rep
    )
    key = jax.device_put(key, rep)

    sub = max(int(sub_rounds), 1)
    rounds = int(rounds_per_block) if rounds_per_block else depth + 1
    drain = _drain_block(
        mesh, axis, spec=spec, be=be, num_devices=num_devices,
        num_inst=num_inst, depth=depth, cap=cap, slots=slots, prow_w=prow_w,
        buckets=buckets, use_chunked=use_chunked, rounds=max(rounds, 1),
        range_size=pm.range_size, num_hubs=num_hubs, sub_rounds=sub,
        search_steps=search_steps, methods=sel_methods,
    )

    blocks = 0
    stats_acc = np.zeros(3, np.int64)
    while True:
        qfields, qcount, qdropped, dfields, dcount, walks, live, dstats = drain(
            indptr_s, iloc_s, iglob_s, wts_s, bias_s, vlo_s,
            prob_s, alias_s, rowmax_s, deg_tgt_s, hubs_d,
            qfields, qcount, qdropped, dfields, dcount,
            walks, key, seeds_d, limits_d, wmax_d,
        )
        blocks += 1
        stats_acc += np.sum(
            np.asarray(jax.device_get(dstats), np.int64), axis=0
        )
        if int(jax.device_get(live)) == 0:
            break
        if blocks >= _MAX_BLOCKS:
            raise RuntimeError(
                f"sharded drain made no global progress after {blocks} "
                f"blocks — exchange_slots={slots} too small?"
            )
    dropped = int(np.sum(jax.device_get(qdropped)))
    if dropped:
        raise RuntimeError(
            f"sharded frontier queues dropped {dropped} walkers — "
            f"queue_capacity={cap} is below the live walker population"
        )
    entry_bytes = ex.entry_nbytes(widths)
    stats = {
        "num_devices": num_devices,
        "exchanged_entries": int(stats_acc[0]),
        "exchange_bytes": int(stats_acc[0]) * entry_bytes,
        "entry_bytes": entry_bytes,
        "hub_hops": int(stats_acc[1]),
        "resident_hops": int(stats_acc[2] - stats_acc[1]),
        "num_hubs": num_hubs,
        "hub_replicated_edges": (
            int(np.sum(np.diff(indptr_np)[hubs_np])) if num_hubs else 0
        ),
        "sub_rounds": sub,
        "blocks": blocks,
    }
    lengths = jnp.sum(walks >= 0, axis=-1)
    return WalkResult(
        walks, lengths, jnp.sum(jnp.maximum(lengths - 1, 0)), stats
    )


# ---------------------------------------------------------------------------
# Replicated-state fallback (opaque-hook programs only) + shard staging helper
# ---------------------------------------------------------------------------


def shard_graph_for_mesh(graph: CSRGraph, num_devices: int):
    """Range-partition a CSR into per-device stacked full-V-indptr CSRs.

    Returns (indptr_stack (D, V+1), indices_stack (D, Emax), weights_stack)
    where each device's slice covers the full vertex-id space with empty rows
    for unowned vertices (so global ids index directly) and edge arrays are
    padded to the max partition size.  Only the :func:`replicated_psum_walk`
    fallback uses this layout; the owner-routed path ships compact hybrid
    CSRs instead (O(V/D + E_D) plus the hub region, DESIGN.md §12/§14).
    """
    parts = partition_by_vertex_range(graph, num_devices)
    v = graph.num_vertices
    emax = max(p.num_edges for p in parts)
    indptrs, indices, weights = [], [], []
    for p in parts:
        full = np.zeros(v + 1, np.int32)
        full[p.vertex_lo + 1 : p.vertex_hi + 1] = p.indptr[1:]
        full[p.vertex_hi + 1 :] = p.indptr[-1]
        indptrs.append(full)
        indices.append(np.pad(p.indices, (0, emax - p.num_edges), constant_values=0).astype(np.int32))
        weights.append(np.pad(p.weights, (0, emax - p.num_edges)).astype(np.float32))
    return (
        jnp.asarray(np.stack(indptrs)),
        jnp.asarray(np.stack(indices)),
        jnp.asarray(np.stack(weights)),
    )


def replicated_psum_walk(
    mesh: Mesh,
    graph: CSRGraph,
    seeds: jax.Array,
    key: jax.Array,
    *,
    depth: int,
    spec: SamplingSpec,
    max_degree: int,
    axis: str = "data",
) -> jax.Array:
    """Walk over a device-sharded graph: owners advance, ``psum`` merges.

    Returns walks (I, depth+1).  Per step each device computes successors for
    walkers whose current vertex it owns (others contribute zeros) and a
    single integer psum replicates the advanced state.  The OPAQUE-program
    fallback of :func:`sharded_random_walk` — the only programs left outside
    the owner-routed envelope: the dense gather evaluates arbitrary user
    hooks that may read any non-resident state, at the cost of replicated
    walker state and one psum per step, and it draws its own RNG pattern
    (not parity-exact with the single-device engine).
    """
    ndev = mesh.shape[axis]
    nvert = graph.num_vertices
    program = tp.lower(spec)
    indptr_s, indices_s, weights_s = shard_graph_for_mesh(graph, ndev)
    # same cached bounds the partitioner used — lo/hi must match the shards
    bounds = PartitionMap.create(nvert, ndev).bounds.astype(np.int32)
    lo = jnp.asarray(bounds[:-1])
    hi = jnp.asarray(bounds[1:])

    @functools.partial(
        shard_map_unchecked,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis), P(), P()),
        out_specs=P(),
    )
    def _run(indptr, indices, wts, lo, hi, seeds, key):
        local = CSRGraph(indptr[0], indices[0], wts[0])
        lo0, hi0 = lo[0], hi[0]
        home = seeds.astype(jnp.int32) if program.carries_home else None

        def step(carry, it):
            cur, prev = carry
            own = (cur >= lo0) & (cur < hi0)
            safe = jnp.where(own, cur, lo0)  # in-range dummy for gathers
            ctx, mask = _edge_ctx(local, safe, prev, it, max_degree, spec.needs_prev_neighbors)
            biases = jnp.where(mask, spec.edge_bias(ctx), 0.0)
            kstep = jax.random.fold_in(key, it)  # same key on all devices
            idx = sel.select_with_replacement(jax.random.fold_in(kstep, 1), biases, mask, 1)[..., 0]
            u = jnp.take_along_axis(ctx.u, idx[..., None], axis=-1)[..., 0]
            alive = own & (cur >= 0) & jnp.any(mask, axis=-1)
            # post-select update through the lowered epilogue (shared with
            # the in-memory engines and the OOM drain, DESIGN.md §10)
            u = jnp.where(
                alive,
                tp.apply_epilogue(
                    jax.random.fold_in(kstep, 2), program, spec, ctx, u, home
                ),
                -1,
            )
            contrib = jnp.where(own, jnp.where(alive, u, -1), 0)
            dead = jax.lax.psum(jnp.where(own, jnp.where(alive, 0, 1), 0), axis)
            nxt = jax.lax.psum(contrib, axis)  # exactly one owner contributes
            nxt = jnp.where((dead > 0) | (cur < 0), -1, nxt)
            return (nxt, cur), nxt

        (_, _), path = jax.lax.scan(
            step, (seeds.astype(jnp.int32), jnp.full(seeds.shape, -1, jnp.int32)), jnp.arange(depth)
        )
        return jnp.concatenate([seeds[None].astype(jnp.int32), path], 0).T

    return _run(indptr_s, indices_s, weights_s, lo, hi, seeds, key)
