"""The one import point for the JAX APIs this repo builds on (jax 0.9.0).

Every module takes ``shard_map``, mesh construction, named-axis queries,
the pytree helpers and the scheduling fence from here, so a future JAX
upgrade touches one file; ``tests/test_compat.py`` fails the suite on a
direct import of the guarded symbols elsewhere.  Consumers do::

    from repro.compat import shard_map, make_mesh, tree_map, ...

Exports
  shard_map               ``jax.shard_map`` (bare or as a decorator).
  make_mesh               ``jax.make_mesh`` with every axis ``AxisType.Auto``.
  axis_size               ``jax.lax.axis_size``.
  tree_map / tree_leaves / tree_flatten / tree_unflatten /
  tree_structure / tree_leaves_with_path / tree_map_with_path / keystr
                          ``jax.tree.*`` and ``jax.tree_util.keystr``.
  FLOAT8_E4M3 / FLOAT8_E5M2
                          the fp8 wire dtypes.
  optimization_barrier    forward-only scheduling fence (see its docstring).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "shard_map", "make_mesh", "axis_size", "tree_map", "tree_leaves",
    "tree_flatten", "tree_unflatten", "tree_structure",
    "tree_leaves_with_path", "tree_map_with_path", "keystr",
    "FLOAT8_E4M3", "FLOAT8_E5M2", "optimization_barrier",
]

shard_map = jax.shard_map
axis_size = jax.lax.axis_size


def make_mesh(axis_shapes, axis_names, *, devices=None, axis_types=None):
    """``jax.make_mesh`` whose axes default to ``AxisType.Auto`` (the
    sharding-propagation semantics every shard_map here assumes)."""
    axis_names = tuple(axis_names)
    if axis_types is None:
        axis_types = (jax.sharding.AxisType.Auto,) * len(axis_names)
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(int(s) for s in axis_shapes), axis_names,
                         axis_types=axis_types, **kw)


@jax.custom_vjp
def _barrier(values):
    return jax.lax.optimization_barrier(values)


def _barrier_fwd(values):
    return jax.lax.optimization_barrier(values), None


def _barrier_bwd(_, ct):
    # The barrier is semantically the identity, so its cotangent is a
    # pass-through.  No fence on the backward: reverse-mode emission
    # order is the autodiff engine's business, not the scheduler's.
    return (ct,)


_barrier.defvjp(_barrier_fwd, _barrier_bwd)


def optimization_barrier(values):
    """Identity on ``values`` (any pytree) that XLA may not reorder
    across: every op producing an input finishes before any op consuming
    an output starts.  The software-pipelined ring transport
    (``repro.core.overlap``) fences its stage ticks with this so the
    compiler cannot re-serialize the interleaved chunk streams.

    Straight-through under autodiff (a ``custom_vjp``): the forward is
    fenced, the backward passes cotangents through unchanged, so the
    ring-attention KV hops that run under ``value_and_grad`` add no
    fences to the backward pass."""
    return _barrier(values)


tree_map = jax.tree.map
tree_leaves = jax.tree.leaves
tree_flatten = jax.tree.flatten
tree_unflatten = jax.tree.unflatten
tree_structure = jax.tree.structure
tree_leaves_with_path = jax.tree.leaves_with_path
tree_map_with_path = jax.tree.map_with_path
keystr = jax.tree_util.keystr

FLOAT8_E4M3 = jnp.float8_e4m3fn
FLOAT8_E5M2 = jnp.float8_e5m2
