"""Logical-axis → mesh sharding rules (DESIGN.md §5).

Params carry logical axis names (models/layers.py ParamDef); this module
maps them to PartitionSpecs for a given mesh, with divisibility-aware
fallback (an axis that does not divide the dim is dropped rather than
letting GSPMD pad — e.g. kv_heads=1 never shards over model=16; the KV
cache shards its *sequence* dim instead: flash-decoding-style split-KV).
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def shard_map_unchecked(f, *, mesh, in_specs, out_specs, axis_names=None):
    """``jax.shard_map`` with the replication check off — the psum-merge
    patterns in this repo are intentionally unreplicated.  ``axis_names``:
    the mesh axes that become manual (None = all of them)."""
    kw = {} if axis_names is None else {"axis_names": axis_names}
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False, **kw
    )


def fsdp_axes(mesh: Mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def default_rules(mesh: Mesh, tp: bool = True) -> dict:
    """logical axis -> tuple of mesh axes (in preference order).

    ``tp=False`` (tp_mode="dp"): the model axis joins the fsdp group —
    right for small archs where TP is pure collective overhead
    (EXPERIMENTS.md §Perf xlstm iterations)."""
    fsdp = fsdp_axes(mesh)
    if not tp:
        full = fsdp + ("model",)
        return {
            "vocab": (), "embed": full, "heads": (), "kv_heads": (),
            "mlp": (), "experts": (), "rnn": (), "layers": (),
            "batch": full, "seq": (), None: (),
        }
    return {
        "vocab": ("model",),
        "embed": fsdp,  # FSDP: shard weight embed dim across data(+pod)
        "heads": ("model",),
        "kv_heads": ("model",),
        "mlp": ("model",),
        "experts": ("model",),
        "rnn": ("model",),
        "layers": (),  # scan axis never sharded
        "batch": fsdp,
        "seq": ("model",),
        None: (),
    }


def _axis_size(mesh: Mesh, names: Sequence[str]) -> int:
    return math.prod(mesh.shape[n] for n in names)


def spec_for(shape: tuple, axes: tuple, mesh: Mesh, rules: Optional[dict] = None) -> P:
    """Build a PartitionSpec for one array, honoring divisibility and the
    at-most-once-per-mesh-axis constraint."""
    rules = rules or default_rules(mesh)
    used: set = set()
    parts = []
    for dim, logical in zip(shape, axes):
        cand = rules.get(logical, ())
        chosen = ()
        # try the full tuple first, then prefixes/suffixes, then single axes
        options = [cand] + [tuple(a for a in cand if a == x) for x in cand]
        for opt in options:
            opt = tuple(a for a in opt if a not in used)
            if opt and dim % _axis_size(mesh, opt) == 0:
                chosen = opt
                break
        used.update(chosen)
        if len(chosen) == 0:
            parts.append(None)
        elif len(chosen) == 1:
            parts.append(chosen[0])
        else:
            parts.append(chosen)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def tree_specs(axes_tree, shapes_tree, mesh: Mesh, rules: Optional[dict] = None):
    """PartitionSpec pytree from (logical axes, shapes) pytrees."""
    return jax.tree_util.tree_map(
        lambda ax, sh: spec_for(tuple(sh.shape), ax, mesh, rules),
        axes_tree,
        shapes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x),
    )


def tree_shardings(specs_tree, mesh: Mesh):
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs_tree)


def batch_spec(mesh: Mesh, batch: Optional[int] = None) -> P:
    axes = fsdp_axes(mesh)
    if batch is not None and batch % _axis_size(mesh, axes) != 0:
        return P()
    return P(axes)


def div_spec(mesh: Mesh, shape: tuple, *parts) -> P:
    """PartitionSpec with non-divisible axes dropped."""
    out = []
    for dim, p in zip(shape, parts):
        if p is None:
            out.append(None)
            continue
        axes = p if isinstance(p, tuple) else (p,)
        if dim % _axis_size(mesh, axes) == 0:
            out.append(p)
        else:
            out.append(None)
    return P(*out)


def cache_spec(shape: tuple, kind: str, mesh: Mesh) -> P:
    """Sharding for decode caches.

    Attention KV (B, S, KVH, Dh): batch→fsdp when divisible; kv_heads→model
    when divisible, else seq→model (split-KV decode); with batch=1 the seq
    dim absorbs the fsdp axes too (sequence parallelism for long_500k).
    Recurrent states (B, ...): batch→fsdp; width dims→model when divisible.
    """
    fsdp = fsdp_axes(mesh)
    used: set = set()
    if kind == "kv" and len(shape) == 4:
        b, s, kvh, hd = shape
        parts: list = [None, None, None, None]
        if b % _axis_size(mesh, fsdp) == 0:
            parts[0] = fsdp if len(fsdp) > 1 else fsdp[0]
            used.update(fsdp)
        if kvh % mesh.shape["model"] == 0:
            parts[2] = "model"
            used.add("model")
        seq_axes = tuple(a for a in (*fsdp, "model") if a not in used)
        if seq_axes and s % _axis_size(mesh, seq_axes) == 0:
            parts[1] = seq_axes if len(seq_axes) > 1 else seq_axes[0]
        return P(*parts)
    # recurrent / generic state: (B, ...) — batch then try model on the last dim
    parts = [None] * len(shape)
    if shape and shape[0] % _axis_size(mesh, fsdp) == 0:
        parts[0] = fsdp if len(fsdp) > 1 else fsdp[0]
    if len(shape) > 1 and shape[-1] % mesh.shape["model"] == 0:
        parts[-1] = "model"
    return P(*parts)
