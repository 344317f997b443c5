"""Shared model layers (explicit-SPMD: every function operates on
per-device local shards inside shard_map; all cross-device movement goes
through the ParallelCtx compressed collectives).

Conventions:
  x_shard : (B, S/tp, D)  sequence-parallel residual stream
  x_full  : (B, S,    D)  after ctx.sp_gather (or tp_f copy in AR mode)
  weights : local shards; fsdp-sharded dims are gathered per-use via
            ctx.weight_gather (whose VJP is the DP grad reduce-scatter)
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.core.telemetry import SCOPE_MLP
COMPUTE_DTYPE = jnp.bfloat16


# --------------------------------------------------------------------------
# Param spec plumbing
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple          # global shape
    fsdp_dim: int | None  # dim sharded over fsdp axes (storage only)
    tp_dim: int | None    # dim sharded over the model axis
    init: str = "normal"  # normal | zeros | ones
    scale: float = 0.02


class ParamBuilder:
    """Collects a nested dict of ParamSpecs."""

    def __init__(self):
        self.specs: dict = {}

    def add(self, name: str, shape, fsdp_dim=None, tp_dim=None,
            init="normal", scale=0.02):
        node = self.specs
        parts = name.split(".")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = ParamSpec(tuple(shape), fsdp_dim, tp_dim, init, scale)

    @staticmethod
    def stack(specs: dict, n: int) -> dict:
        """Add a leading layer dim of size n to every spec (scan layout)."""
        def f(s: ParamSpec) -> ParamSpec:
            return ParamSpec(
                (n,) + s.shape,
                None if s.fsdp_dim is None else s.fsdp_dim + 1,
                None if s.tp_dim is None else s.tp_dim + 1,
                s.init, s.scale)
        return compat.tree_map(f, specs,
                            is_leaf=lambda x: isinstance(x, ParamSpec))


def init_param(key, spec: ParamSpec, dtype=COMPUTE_DTYPE):
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    return (jax.random.normal(key, spec.shape, jnp.float32)
            * spec.scale).astype(dtype)


def init_params(specs, rng, dtype=COMPUTE_DTYPE):
    leaves, treedef = compat.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(rng, len(leaves))
    vals = [init_param(k, s, dtype) for k, s in zip(keys, leaves)]
    return compat.tree_unflatten(treedef, vals)


def partition_spec(spec: ParamSpec, fsdp_axes: tuple, tp_axis: str):
    """ParamSpec -> jax PartitionSpec for storage sharding."""
    from jax.sharding import PartitionSpec as P
    dims = [None] * len(spec.shape)
    if spec.fsdp_dim is not None and fsdp_axes:
        dims[spec.fsdp_dim] = fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]
    if spec.tp_dim is not None:
        dims[spec.tp_dim] = tp_axis
    return P(*dims)


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def rmsnorm(x, scale, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layernorm(x, scale, bias, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))
            + bias.astype(jnp.float32)).astype(x.dtype)


def norm_specs(pb: ParamBuilder, name: str, d: int, kind: str):
    pb.add(f"{name}.scale", (d,), init="zeros")
    if kind == "layernorm":
        pb.add(f"{name}.bias", (d,), init="zeros")


def apply_norm(x, p, kind: str, eps: float):
    if kind == "layernorm":
        return layernorm(x, p["scale"], p["bias"], eps)
    return rmsnorm(x, p["scale"], eps)


# --------------------------------------------------------------------------
# Positional encodings
# --------------------------------------------------------------------------

def rope_freqs(hd: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, hd, 2) / hd))


def apply_rope(x, positions, theta: float):
    """x (B, S, H, hd), positions (S,) or (B, S)."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta), jnp.float32)
    if positions.ndim == 1:
        ang = positions[None, :, None] * freqs[None, None, :]
        ang = ang[:, :, None, :]                       # (1, S, 1, hd/2)
    else:
        ang = positions[:, :, None] * freqs[None, None, :]
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoid_pos(seq: int, d: int, offset: int = 0):
    pos = np.arange(offset, offset + seq)[:, None]
    div = np.exp(np.arange(0, d, 2) / d * -np.log(10000.0))[None, :]
    table = np.zeros((seq, d), np.float32)
    table[:, 0::2] = np.sin(pos * div)
    table[:, 1::2] = np.cos(pos * div)
    return jnp.asarray(table, COMPUTE_DTYPE)


# --------------------------------------------------------------------------
# MLP (gated + plain variants)
# --------------------------------------------------------------------------

def mlp_specs(pb: ParamBuilder, name: str, d: int, f: int, kind: str):
    if kind in ("swiglu", "geglu"):
        pb.add(f"{name}.w1", (d, f), fsdp_dim=0, tp_dim=1)
        pb.add(f"{name}.w3", (d, f), fsdp_dim=0, tp_dim=1)
    else:
        pb.add(f"{name}.w1", (d, f), fsdp_dim=0, tp_dim=1)
        pb.add(f"{name}.b1", (f,), tp_dim=0, init="zeros")
        pb.add(f"{name}.b2", (d,), init="zeros")
    pb.add(f"{name}.w2", (f, d), fsdp_dim=1, tp_dim=0)


@jax.named_scope(SCOPE_MLP)
def mlp_apply(x_full, p, kind: str, ctx):
    """x_full (B, S, D) -> partial (B, S, D) — caller reduces over tp."""
    w1 = ctx.weight_gather(p["w1"], 0)
    w2 = ctx.weight_gather(p["w2"], 1)
    if kind in ("swiglu", "geglu"):
        w3 = ctx.weight_gather(p["w3"], 0)
        h = x_full @ w1
        g = x_full @ w3
        act = jax.nn.silu(h) if kind == "swiglu" else jax.nn.gelu(h)
        y = (act * g) @ w2
    else:
        h = x_full @ w1 + p["b1"].astype(x_full.dtype)
        y = jax.nn.gelu(h) @ w2
        # b2 replicated: add AFTER the tp reduction — handled by caller flag
    return y


# --------------------------------------------------------------------------
# Vocab-parallel embedding + LM head with distributed cross-entropy
# --------------------------------------------------------------------------

def embed_specs(pb: ParamBuilder, vocab_pad: int, d: int, tie: bool):
    pb.add("embed.table", (vocab_pad, d), fsdp_dim=1, tp_dim=0, scale=0.02)
    if not tie:
        pb.add("head.table", (vocab_pad, d), fsdp_dim=1, tp_dim=0, scale=0.02)


def embed_lookup(tokens, table_local, ctx, plan):
    """tokens (B, S) -> x_shard (B, S/tp, D). Vocab-parallel: each device
    resolves its vocab slice, the partial sums are reduced AND seq-scattered
    by a single compressed reduce-scatter (TACO site #1)."""
    table = ctx.weight_gather(table_local, 1)          # (V/tp, D)
    v_loc = table.shape[0]
    idx = jax.lax.axis_index(ctx.tp_axis)
    shifted = tokens - idx * v_loc
    valid = (shifted >= 0) & (shifted < v_loc)
    partial = jnp.take(table, jnp.clip(shifted, 0, v_loc - 1), axis=0)
    partial = jnp.where(valid[..., None], partial, 0).astype(COMPUTE_DTYPE)
    return ctx.sp_scatter(partial, 1)                  # (B, S/tp, D)


def vocab_parallel_xent(x_full, table_local, labels, mask, ctx, plan,
                        chunk: int = 512):
    """x_full (B, S, D), labels (B, S) -> (sum_loss, sum_count) local.

    Logits are computed per vocab shard; softmax statistics are combined
    with three tiny f32 psums per chunk (these are O(B*S) scalars, not
    intermediate tensors — left uncompressed, like the paper)."""
    from repro.models import analysis_mode
    table = ctx.weight_gather(table_local, 1)          # (V/tp, D)
    v_loc = table.shape[0]
    idx = jax.lax.axis_index(ctx.tp_axis)
    b, s, d = x_full.shape
    if analysis_mode.on():
        chunk = s  # single trip: exact cost analysis
    chunk = min(chunk, s)
    n_chunks = s // chunk if s % chunk == 0 else 1
    if s % chunk != 0:
        chunk = s

    from repro.core.collectives import psum_exact

    def chunk_loss(xc, yc, mc):
        logits = (xc @ table.T).astype(jnp.float32)    # (B, c, V/tp)
        # numerical-stability shift only — gradient-free by construction
        # (stop_gradient BEFORE pmax: symbolic-zero tangent skips the
        # missing pmax JVP rule)
        m = jax.lax.pmax(
            jax.lax.stop_gradient(jnp.max(logits, axis=-1)), ctx.tp_axis)
        z = psum_exact(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1),
                       ctx.tp_axis)
        shifted = yc - idx * v_loc
        valid = (shifted >= 0) & (shifted < v_loc)
        picked = jnp.take_along_axis(
            logits, jnp.clip(shifted, 0, v_loc - 1)[..., None], axis=-1)[..., 0]
        label_logit = psum_exact(jnp.where(valid, picked, 0.0), ctx.tp_axis)
        nll = (jnp.log(z) + m) - label_logit
        return jnp.sum(nll * mc), jnp.sum(mc)

    xs = x_full.reshape(b, n_chunks, chunk, d).swapaxes(0, 1)
    ys = labels.reshape(b, n_chunks, chunk).swapaxes(0, 1)
    ms = mask.reshape(b, n_chunks, chunk).swapaxes(0, 1).astype(jnp.float32)

    def body(carry, inp):
        xc, yc, mc = inp
        l, c = jax.checkpoint(chunk_loss)(xc, yc, mc)
        return (carry[0] + l, carry[1] + c), None

    (loss, count), _ = jax.lax.scan(body, (0.0, 0.0), (xs, ys, ms))
    return loss, count


def lm_head_logits(x, table_local, ctx):
    """Decode-path local logits (B, 1, V/tp)."""
    table = ctx.weight_gather(table_local, 1)
    return (x @ table.T).astype(jnp.float32)


def distributed_argmax(logits, ctx):
    """logits (B, 1, V/tp) -> global argmax token ids (B, 1)."""
    v_loc = logits.shape[-1]
    idx = jax.lax.axis_index(ctx.tp_axis)
    local_val = jnp.max(logits, axis=-1)
    local_arg = jnp.argmax(logits, axis=-1) + idx * v_loc
    vals = jax.lax.all_gather(local_val, ctx.tp_axis)   # (tp, B, 1) tiny
    args = jax.lax.all_gather(local_arg, ctx.tp_axis)
    best = jnp.argmax(vals, axis=0)
    return jnp.take_along_axis(args, best[None], axis=0)[0]
