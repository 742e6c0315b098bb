"""Transition programs: lowering the three-hook API onto the fast path.

C-SAW's user API is three opaque callables (vertex bias, edge bias, update —
``core.api``).  Opaque hooks force the engines onto the dense full-context
gather: every step materializes ``(W, max_degree)`` neighbor/weight/degree
tensors just to evaluate a bias that is usually one of a handful of shapes.
This module closes that gap with a small declarative IR — the **transition
program** — that names what a spec's hooks actually consume, so the backend
can compile the step instead of interpreting it:

Bias sources (where the per-edge transition bias comes from):

  - :class:`FlatBias`    — a static ``(E,)`` CSR-order array (deepwalk,
    weighted/biased walks).  Sampled straight off the flat edge arrays by the
    degree-bucketed scheduler; no neighbor tensors ever exist.
  - :class:`WindowBias`  — a dynamic function of the walker's *gathered
    neighbor window* and carried state (prev vertex): node2vec and friends.
    Evaluated per degree bucket on the kernel's block-aligned edge windows
    (``(W, 2·seg)`` per cohort), never on a dense ``max_degree`` gather.
  - :class:`OpaqueBias`  — anything else; the dense gather survives only as
    this fallback.

Epilogues (what happens after the ITS draw picks neighbor ``u``):

  - :class:`IdentityEpilogue` — walk to ``u``.
  - :class:`MHAcceptEpilogue` — Metropolis-Hastings: accept ``u`` w.p.
    ``min(1, deg(v)/deg(u))``, else stay at ``v``.
  - :class:`TeleportEpilogue` — with probability ``prob`` go elsewhere:
    a uniform random vertex (jump), a fixed vertex (restart), or the
    walk's own seed (``"home"`` restart).
  - :class:`OpaqueEpilogue`   — defer to ``spec.update`` (full generality).

All epilogues lower to one fused post-select jnp step
(:func:`apply_epilogue`) shared by ``engine.random_walk``,
``engine.traversal_sample`` and the ``oom`` drain loop, and consume the same
counted RNG on every backend, so reference and Pallas walks stay
bit-identical.

State carried across steps is part of the program: the previous vertex is
always threaded through the engines' scan carries (every bias may read it),
``carries_home`` (teleport-to-seed) tells them to also thread the
per-instance home vertex; the per-instance RNG budget is the counted-RNG
contract the backends already share (``select.retry_randoms``).

Specs *declare* their program (``SamplingSpec.transition``); legacy specs
without a declaration are inferred by :func:`lower` from the PR-1 era flags
(``flat_edge_bias`` ⇒ flat, else opaque) so external code keeps working.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Literal, Optional, Union

import jax
import jax.numpy as jnp

from repro.core.api import EdgeCtx, SamplingSpec, identity_update

# ---------------------------------------------------------------------------
# Bias sources
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FlatBias:
    """Static per-edge bias: ``fn(graph) -> (E,)`` float32 in CSR order.

    Must satisfy ``fn(g)[e] == spec.edge_bias(ctx)`` for every real edge
    ``e`` (the PR-1 ``flat_edge_bias`` contract).
    """

    fn: Callable[[object], jax.Array]


@dataclasses.dataclass(frozen=True)
class WindowBias:
    """Dynamic per-edge bias evaluated on gathered neighbor windows.

    ``fn`` is an :class:`~repro.core.api.EdgeBiasFn` — it receives an
    ``EdgeCtx`` whose neighbor axis is a degree-bucket *window* (block-aligned
    ``(W, 2·seg)`` slices of the CSR edge arrays, or ``(W, chunk)`` slices on
    the huge-degree two-pass tail) instead of a dense ``(W, max_degree)``
    gather.  The bias of each candidate must depend only on per-edge context
    (``u``, ``weight``, ``deg_u``, ``is_prev_neighbor``) and per-walker state
    (``v``, ``prev``, ``deg_v``, ``depth``) — i.e. it must be *rankable
    per-window*, which every EDGEBIAS of the paper's Table I is.

    The previous vertex is always available (the walk engines carry it for
    every spec); ``needs_prev_neighbors`` requests the ``is_prev_neighbor``
    field — on the windowed path membership is a per-candidate binary search
    over ``prev``'s sorted CSR row (O(D·log deg) instead of the dense path's
    O(D²) compare).  ``needs_deg_u=False`` declares the hook never reads
    ``ctx.deg_u`` and skips two window-wide degree gathers per cohort (it
    reads as zeros).

    ``max_ratio`` declares ``fn(ctx) <= max_ratio * ctx.weight`` on every
    edge.  With it, a walker on a row wider than the top bucket draws by
    rejection against ``max_ratio * max(weight)`` — a few hook evaluations
    instead of a scan of the whole row — and only walkers whose counted
    budget runs out take the exact two-pass scan, so the draw stays exact.
    """

    fn: Callable[[EdgeCtx], jax.Array]
    needs_prev_neighbors: bool = False
    needs_deg_u: bool = True
    max_ratio: float | None = None


@dataclasses.dataclass(frozen=True)
class OpaqueBias:
    """Fallback: evaluate ``spec.edge_bias`` on the dense full-context gather."""


BiasSource = Union[FlatBias, WindowBias, OpaqueBias]


# ---------------------------------------------------------------------------
# Epilogues
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class IdentityEpilogue:
    """Walk to the selected neighbor."""


@dataclasses.dataclass(frozen=True)
class MHAcceptEpilogue:
    """Metropolis-Hastings acceptance: keep ``u`` w.p. ``min(1, deg_v/deg_u)``,
    else stay at ``v`` (paper Table I, MHRW)."""


@dataclasses.dataclass(frozen=True)
class TeleportEpilogue:
    """With probability ``prob`` replace ``u`` by a teleport target.

    target="uniform": a uniform random vertex in ``[0, num_vertices)`` (jump);
    target="fixed":   the predetermined ``vertex`` (restart);
    target="home":    the walk's own seed vertex (restart-to-home) — engines
                      thread the per-instance home array through their carry.
    """

    prob: float
    target: Literal["uniform", "fixed", "home"] = "uniform"
    vertex: int = -1
    num_vertices: int = 0

    def __post_init__(self):
        if self.target == "uniform" and self.num_vertices <= 0:
            raise ValueError(
                "TeleportEpilogue(target='uniform') needs num_vertices > 0 "
                "(randint over an empty range would silently teleport every "
                "jumper to vertex 0)"
            )
        if self.target == "fixed" and self.vertex < 0:
            raise ValueError("TeleportEpilogue(target='fixed') needs vertex >= 0")


@dataclasses.dataclass(frozen=True)
class OpaqueEpilogue:
    """Fallback: call ``spec.update`` (arbitrary user code)."""


Epilogue = Union[IdentityEpilogue, MHAcceptEpilogue, TeleportEpilogue, OpaqueEpilogue]


# ---------------------------------------------------------------------------
# The program
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TransitionProgram:
    """One walk step, declaratively: bias source + carried state + epilogue.

    Frozen/hashable so it rides inside ``SamplingSpec`` as a jit static
    argument, exactly like the hook fields it lowers.
    """

    bias: BiasSource
    epilogue: Epilogue = IdentityEpilogue()
    #: Selection method for the per-degree-bucket scheduler (DESIGN.md §13):
    #: ``"auto"`` lets the cost model pick per bucket (static FlatBias →
    #: alias tables, near-uniform bias → rejection, dynamic WindowBias →
    #: ITS); ``"its"``/``"alias"``/``"rejection"`` force one method for
    #: every bucket.  Only the flat-bias fast path consults it — window and
    #: opaque modes are inherently dynamic and always use ITS.
    method: str = "auto"

    def __post_init__(self):
        if self.method not in ("auto", "its", "alias", "rejection"):
            raise ValueError(
                f"unknown selection method {self.method!r}; expected one of "
                "'auto', 'its', 'alias', 'rejection'"
            )

    @property
    def carries_home(self) -> bool:
        return (
            isinstance(self.epilogue, TeleportEpilogue)
            and self.epilogue.target == "home"
        )

    @property
    def mode(self) -> str:
        """Engine dispatch mode: ``"flat"`` / ``"window"`` run the
        degree-bucketed fast path, ``"opaque"`` the dense-gather fallback."""
        if isinstance(self.bias, FlatBias):
            return "flat"
        if isinstance(self.bias, WindowBias):
            return "window"
        return "opaque"


def lower(spec: SamplingSpec) -> TransitionProgram:
    """Compile a spec's hooks into a transition program.

    A declared ``spec.transition`` wins.  Otherwise the legacy flags are
    lowered: ``flat_edge_bias`` ⇒ :class:`FlatBias` (the PR-1 fast-path
    contract), anything else ⇒ :class:`OpaqueBias`; an ``update`` other than
    ``identity_update`` ⇒ :class:`OpaqueEpilogue`.  Inference cannot prove a
    hook windowable — only declarations reach the :class:`WindowBias` path.
    """
    override = getattr(spec, "selection_method", None)
    if spec.transition is not None:
        prog = spec.transition
        if override is not None and override != prog.method:
            prog = dataclasses.replace(prog, method=override)
        return prog
    if spec.flat_edge_bias is not None and not spec.needs_prev_neighbors:
        bias: BiasSource = FlatBias(spec.flat_edge_bias)
    else:
        bias = OpaqueBias()
    epi: Epilogue = (
        IdentityEpilogue() if spec.update is identity_update else OpaqueEpilogue()
    )
    return TransitionProgram(bias=bias, epilogue=epi, method=override or "auto")


# ---------------------------------------------------------------------------
# The fused post-select epilogue
# ---------------------------------------------------------------------------


def apply_epilogue(
    key: jax.Array,
    program: TransitionProgram,
    spec: SamplingSpec,
    ctx: EdgeCtx,
    u: jax.Array,
    home: Optional[jax.Array] = None,
) -> jax.Array:
    """Lowered UPDATE: one fused jnp step shared by every engine.

    ``ctx`` is the (possibly minimal, D=1) EdgeCtx of the selected edge and
    ``u`` the selected neighbor (same shape as ``ctx.v``; -1 for dead
    walkers — epilogues must preserve -1).  ``home`` is the per-instance home
    vertex array, required iff ``program.carries_home``.  RNG: exactly one
    ``key`` per step, consumed identically on every backend.
    """
    epi = program.epilogue
    if isinstance(epi, IdentityEpilogue):
        return u
    if isinstance(epi, MHAcceptEpilogue):
        deg_u = _selected_deg_u(ctx, u)
        stay = mh_stay(jax.random.uniform(key, u.shape), ctx.deg_v, deg_u)
        return jnp.where(stay & (ctx.v >= 0) & (u >= 0), ctx.v, u)
    if isinstance(epi, TeleportEpilogue):
        kj, kv = jax.random.split(key)
        teleport = jax.random.uniform(kj, u.shape) < epi.prob
        if epi.target == "uniform":
            tgt = jax.random.randint(kv, u.shape, 0, epi.num_vertices)
        elif epi.target == "fixed":
            tgt = jnp.full_like(u, epi.vertex)
        else:  # "home"
            if home is None:
                raise ValueError(
                    "TeleportEpilogue(target='home') needs the per-instance "
                    "home array; this engine does not carry one"
                )
            tgt = jnp.broadcast_to(jnp.expand_dims(home, tuple(range(home.ndim, u.ndim))), u.shape)
        return jnp.where(teleport & (u >= 0), tgt, u)
    # OpaqueEpilogue — full generality through the user hook
    return spec.update(key, ctx, u)


def mh_stay(r: jax.Array, deg_v: jax.Array, deg_u: jax.Array) -> jax.Array:
    """The MH acceptance test, in one place: stay iff ``r >= min(1,
    deg_v/deg_u)`` (paper Table I, MHRW).

    ``deg_v``/``deg_u`` are int32 true degrees; the division promotes to
    float32 exactly like the engine's fused epilogue, so every caller —
    ``apply_epilogue`` here, the owner-routed sharded drain
    (``shard/walk.py``, which resolves ``deg_u`` from its replicated hub /
    resident-row degree lanes) — decides acceptance with bit-identical
    arithmetic from the same counted uniform.
    """
    accept_p = jnp.minimum(1.0, deg_v / jnp.maximum(deg_u, 1))
    return r >= accept_p


def _selected_deg_u(ctx: EdgeCtx, u: jax.Array) -> jax.Array:
    """deg(u) for the selected neighbor, from whatever ctx the path built.

    Fast paths hand a minimal D=1 ctx (``ctx.u == u[..., None]``); the dense
    path hands the full window — locate ``u`` in it (the same arithmetic the
    legacy MHRW hook used).
    """
    if ctx.u.shape[-1] == 1:
        return ctx.deg_u[..., 0]
    pos = jnp.argmax(ctx.u == u[..., None], axis=-1)
    return jnp.where(
        u >= 0,
        jnp.take_along_axis(ctx.deg_u, pos[..., None], axis=-1)[..., 0],
        1,
    )
