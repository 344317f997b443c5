"""AdamW with ZeRO-1 sharded state, designed to run INSIDE shard_map.

State layout: fp32 master weights + both moments stored with exactly the
same (fsdp, model) sharding as the bf16 params — i.e. optimizer state is
fully sharded (ZeRO-1); the DP gradient reduction itself falls out of the
weight-gather transpose (ZeRO-2, see core/parallel.py) and is SDP4bit-
compressible.

All update math is element-wise on local shards. The only cross-device
work is the spec-aware global-norm clip (one scalar psum) and the
replicated-param gradient correction (``finalize_grads``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.layers import ParamSpec
from repro import compat
from repro.core.telemetry import SCOPE_OPTIM

IS_SPEC = lambda x: isinstance(x, ParamSpec)  # noqa: E731


@dataclasses.dataclass(frozen=True)
class OptConfig:
    lr_max: float = 3e-4
    lr_min: float = 3e-5
    warmup_steps: int = 100
    total_steps: int = 10_000
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0


def schedule(step, oc: OptConfig):
    """Linear warmup -> cosine decay (paper: 3e-4 -> 3e-5)."""
    step = step.astype(jnp.float32)
    warm = oc.lr_max * step / max(oc.warmup_steps, 1)
    t = jnp.clip((step - oc.warmup_steps)
                 / max(oc.total_steps - oc.warmup_steps, 1), 0.0, 1.0)
    cos = oc.lr_min + 0.5 * (oc.lr_max - oc.lr_min) * (1 + jnp.cos(jnp.pi * t))
    return jnp.where(step < oc.warmup_steps, warm, cos)


def init_opt_state(params):
    master = compat.tree_map(lambda p: p.astype(jnp.float32), params)
    zeros = compat.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return {"master": master, "mu": zeros,
            "nu": compat.tree_map(jnp.copy, zeros),
            "step": jnp.zeros((), jnp.int32)}


def abstract_opt_state(abstract_params):
    f32 = compat.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), abstract_params)
    return {"master": f32, "mu": f32, "nu": f32,
            "step": jax.ShapeDtypeStruct((), jnp.int32)}


def opt_state_pspecs(param_pspecs):
    from jax.sharding import PartitionSpec as P
    return {"master": param_pspecs, "mu": param_pspecs, "nu": param_pspecs,
            "step": P()}


@jax.named_scope(SCOPE_OPTIM)
def finalize_grads(grads, model):
    """psum grads of replicated-but-divergently-used params (norm scales,
    replicated-kv weights, router) over the axes they're replicated on."""
    specs = model.specs()

    def fix(g, s):
        axes = model.replicated_grad_axes(s)
        return jax.lax.psum(g, axes) if axes else g

    return compat.tree_map(fix, grads, specs, is_leaf=IS_SPEC)


def global_grad_norm(grads, model):
    """Spec-aware global L2 norm: sharded dims psum'd, replicated not."""
    specs = model.specs()
    sq = jnp.zeros((), jnp.float32)
    flat_g = compat.tree_leaves(grads)
    flat_s = compat.tree_leaves(specs, is_leaf=IS_SPEC)
    local = jnp.zeros((), jnp.float32)
    shard_axes_terms = {}
    for g, s in zip(flat_g, flat_s):
        axes = []
        if s.fsdp_dim is not None:
            axes.extend(model.fsdp_axes)
        if s.tp_dim is not None:
            axes.append(model.tp_axis)
        key = tuple(axes)
        shard_axes_terms.setdefault(key, []).append(
            jnp.sum(g.astype(jnp.float32) ** 2))
    for axes, terms in shard_axes_terms.items():
        t = sum(terms)
        if axes:
            t = jax.lax.psum(t, tuple(axes))
        sq = sq + t
    del local
    return jnp.sqrt(sq)


@jax.named_scope(SCOPE_OPTIM)
def adamw_update(grads, opt_state, oc: OptConfig, model):
    """grads: finalized local-shard grads. Returns (new_bf16_params,
    new_opt_state, metrics)."""
    step = opt_state["step"] + 1
    lr = schedule(step, oc)
    gnorm = global_grad_norm(grads, model)
    scale = jnp.minimum(1.0, oc.clip_norm / jnp.maximum(gnorm, 1e-12))

    b1, b2 = oc.b1, oc.b2
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)

    def upd(g, m, mu, nu):
        g = g.astype(jnp.float32) * scale
        mu = b1 * mu + (1 - b1) * g
        nu = b2 * nu + (1 - b2) * g * g
        update = (mu / bc1) / (jnp.sqrt(nu / bc2) + oc.eps)
        m = m - lr * (update + oc.weight_decay * m)
        return m, mu, nu

    out = compat.tree_map(upd, grads, opt_state["master"], opt_state["mu"],
                       opt_state["nu"])
    # out mirrors the tree with (m, mu, nu) tuples at leaves
    leaves, treedef = compat.tree_flatten(
        out, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 3
        and all(hasattr(t, "dtype") for t in x))
    master = compat.tree_unflatten(treedef, [l[0] for l in leaves])
    mu = compat.tree_unflatten(treedef, [l[1] for l in leaves])
    nu = compat.tree_unflatten(treedef, [l[2] for l in leaves])
    new_params = compat.tree_map(lambda m: m.astype(jnp.bfloat16), master)
    new_state = {"master": master, "mu": mu, "nu": nu, "step": step}
    return new_params, new_state, {"grad_norm": gnorm, "lr": lr}
