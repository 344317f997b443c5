"""TP-sharded GQA attention with head padding / KV replication.

Head layout (DESIGN.md §4): q heads padded to a multiple of tp. If
n_kv >= tp the kv heads are group-padded and sharded alongside q; else the
(few) kv heads are stored replicated across the model axis and each device
statically selects the kv head(s) its local q heads map to.

The attention core is a two-level chunked scan in pure JAX: an online
softmax over kv chunks with f32 accumulators, inside a map over q chunks.
Its backward is flash-style (a custom VJP): it recomputes each chunk
pair's probabilities from the saved per-row log-sum-exp, so no
O(Sq*Sk) buffer is stored for it. Sliding-window attention slices a
static (W + Cq)-wide kv window per q chunk, giving true O(S*W) cost —
this is what qualifies SWA archs for long_500k.

Dead (padding) q heads are masked out of the output so their parameter
gradients are exactly zero (keeps padded model == unpadded reference).
"""
from __future__ import annotations

import collections
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.telemetry import SCOPE_ATTN, SCOPE_ATTN_CORE
from repro.models.layers import COMPUTE_DTYPE, apply_rope

NEG_INF = -1e30
#: Opened inside ``attn`` (attention_apply), so op_names read SCOPE_ATTN_CORE.
_SCOPE_CORE = SCOPE_ATTN_CORE.rpartition("/")[2]


# --------------------------------------------------------------------------
# specs
# --------------------------------------------------------------------------

def attn_specs(pb, name: str, cfg, plan):
    d, hd = cfg.d_model, cfg.hd
    pb.add(f"{name}.wq", (d, plan.heads_pad * hd), fsdp_dim=0, tp_dim=1)
    kv_dim = plan.kv_pad * hd
    kv_tp = 1 if plan.kv_mode == "sharded" else None
    pb.add(f"{name}.wk", (d, kv_dim), fsdp_dim=0, tp_dim=kv_tp)
    pb.add(f"{name}.wv", (d, kv_dim), fsdp_dim=0, tp_dim=kv_tp)
    pb.add(f"{name}.wo", (plan.heads_pad * hd, d), fsdp_dim=1, tp_dim=0)
    if cfg.qkv_bias:
        bias_tp = 0 if kv_tp is not None else None
        pb.add(f"{name}.bq", (plan.heads_pad * hd,), tp_dim=0, init="zeros")
        pb.add(f"{name}.bk", (kv_dim,), tp_dim=bias_tp, init="zeros")
        pb.add(f"{name}.bv", (kv_dim,), tp_dim=bias_tp, init="zeros")


def _local_head_ids(plan, ctx):
    """Global q-head ids held by this device, and their validity mask."""
    idx = jax.lax.axis_index(ctx.tp_axis)
    ids = idx * plan.q_local + jnp.arange(plan.q_local)
    return ids


def head_mask(plan, ctx, n_heads: int):
    return (_local_head_ids(plan, ctx) < n_heads).astype(COMPUTE_DTYPE)


def _expand_kv(k, plan, ctx, cfg):
    """k (B, S, kv_local, hd) -> (B, S, q_local, hd), aligned to the
    device's local q heads."""
    if plan.kv_mode == "sharded":
        gsz = plan.group_size
        return jnp.repeat(k, gsz, axis=2) if gsz > 1 else k
    # replicated mode: kv head for global q head h is h // gsz (dead q heads
    # clamp to the last kv head; their output is masked anyway)
    gsz = max(cfg.n_heads // max(cfg.n_kv_heads, 1), 1)
    ids = _local_head_ids(plan, ctx)
    kv_ids = jnp.clip(ids // gsz, 0, plan.kv_local - 1)
    return jnp.take(k, kv_ids, axis=2)


# --------------------------------------------------------------------------
# QKV projection
# --------------------------------------------------------------------------

def q_project(x_full, p, cfg, plan, ctx, positions):
    b, s, _ = x_full.shape
    hd = cfg.hd
    wq = ctx.weight_gather(p["wq"], 0)
    q = x_full @ wq
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
    q = q.reshape(b, s, plan.q_local, hd)
    if cfg.pos == "rope" and positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
    return q


def kv_project(x_kv, p, cfg, plan, ctx, positions):
    """positions=None skips rope (cross-attention keys)."""
    b, s, _ = x_kv.shape
    hd = cfg.hd
    wk = ctx.weight_gather(p["wk"], 0)
    wv = ctx.weight_gather(p["wv"], 0)
    k = x_kv @ wk
    v = x_kv @ wv
    if cfg.qkv_bias:
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    k = k.reshape(b, s, -1, hd)
    v = v.reshape(b, s, -1, hd)
    if cfg.pos == "rope" and positions is not None:
        k = apply_rope(k, positions, cfg.rope_theta)
    return k, v


def qkv_project(x_full, p, cfg, plan, ctx, positions):
    q = q_project(x_full, p, cfg, plan, ctx, positions)
    k, v = kv_project(x_full, p, cfg, plan, ctx, positions)
    return q, k, v


# --------------------------------------------------------------------------
# chunked attention core: online-softmax forward, recompute backward
# --------------------------------------------------------------------------

#: Trace-time count of the backward each ``attention_core`` call took:
#: ``recompute`` (the custom VJP) or ``autodiff`` (analysis mode only).
BACKWARD_TRACES: collections.Counter = collections.Counter()


class _Chunks(NamedTuple):
    """Static chunking of one core call."""
    causal: bool
    window: int | None
    q_chunk: int
    kv_chunk: int
    nq: int
    unroll: bool        # python-unrolled q chunks (analysis mode)


def _chunked(x, n):
    """(B,H,S,...) -> (n,B,H,S/n,...)."""
    b, h, s = x.shape[:3]
    return jnp.moveaxis(x.reshape(b, h, n, s // n, *x.shape[3:]), 2, 0)


def _unchunked(xs):
    """Inverse of :func:`_chunked`."""
    n, b, h, c = xs.shape[:4]
    return jnp.moveaxis(xs, 0, 2).reshape(b, h, n * c, *xs.shape[4:])


def _kv_span(c, qi, sk, q_offset, kv_len):
    """Query chunk ``qi``'s kv range ``[lo, lo + width)`` and its additive
    mask ``mask_fn(kv_start, ck) -> (Cq, ck)`` (kv_start relative to lo).
    Sliding windows take a static (W + Cq)-wide range."""
    q_pos = q_offset + qi * c.q_chunk + jnp.arange(c.q_chunk)
    if c.window is None:
        lo, width = 0, sk
    else:
        w = min(c.window, sk)
        width = min(w + c.q_chunk, sk)
        lo = jnp.clip(q_pos[0] - w + 1, 0, sk - width)

    def mask_fn(kv_start, ck):
        kpos = kv_start + jnp.arange(ck)
        if c.window is not None:
            kpos = lo + kpos
        m = jnp.zeros((c.q_chunk, ck), jnp.float32)
        if c.causal or c.window is not None:
            m = jnp.where(kpos[None, :] > q_pos[:, None], NEG_INF, m)
        if c.window is not None:
            m = jnp.where(kpos[None, :] <= q_pos[:, None] - w, NEG_INF, m)
        if kv_len is not None:
            m = jnp.where(kpos[None, :] >= kv_len, NEG_INF, m)
        return m

    return lo, width, mask_fn


def _kv_slice(x, lo, width):
    if width == x.shape[2]:
        return x
    return jax.lax.dynamic_slice_in_dim(x, lo, width, axis=2)


def _softmax_scan(q, k, v, mask_fn, kv_chunk: int):
    """q (B,H,Cq,hd) vs k,v (B,H,Sk,hd) -> (B,H,Cq,hd) and the rows'
    log-sum-exp (B,H,Cq). Online softmax over kv chunks."""
    b, h, cq, hd = q.shape
    sk = k.shape[2]
    kv_chunk = min(kv_chunk, sk)
    n = sk // kv_chunk
    scale = 1.0 / np.sqrt(hd)
    qf = q.astype(jnp.float32) * scale

    def body(carry, inp):
        acc, m, l = carry
        kc, vc, j = inp
        s_ = jnp.einsum("bhqd,bhkd->bhqk", qf, kc.astype(jnp.float32))
        s_ = s_ + mask_fn(j * kv_chunk, kv_chunk)[None, None]
        m_new = jnp.maximum(m, jnp.max(s_, axis=-1))
        p_ = jnp.exp(s_ - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p_, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p_, vc.astype(jnp.float32))
        return (acc, m_new, l), None

    init = (jnp.zeros((b, h, cq, hd), jnp.float32),
            jnp.full((b, h, cq), NEG_INF, jnp.float32),
            jnp.zeros((b, h, cq), jnp.float32))
    (acc, m, l), _ = jax.lax.scan(
        body, init, (_chunked(k, n), _chunked(v, n), jnp.arange(n)))
    return acc / jnp.maximum(l, 1e-30)[..., None], m + jnp.log(l)


def _softmax_scan_bwd(q, k, v, do, lse, delta, mask_fn, kv_chunk: int):
    """Backward of :func:`_softmax_scan` for one q chunk: each kv chunk's
    probabilities are recomputed from the rows' log-sum-exp, never
    stored. Returns f32 dq (B,H,Cq,hd) and dk, dv (B,H,Sk,hd)."""
    b, h, cq, hd = q.shape
    sk = k.shape[2]
    kv_chunk = min(kv_chunk, sk)
    n = sk // kv_chunk
    scale = 1.0 / np.sqrt(hd)
    qf = q.astype(jnp.float32) * scale

    def body(dq, inp):
        kc, vc, j = inp
        kf, vf = kc.astype(jnp.float32), vc.astype(jnp.float32)
        s_ = jnp.einsum("bhqd,bhkd->bhqk", qf, kf)
        s_ = s_ + mask_fn(j * kv_chunk, kv_chunk)[None, None]
        p_ = jnp.exp(s_ - lse[..., None])
        dp = jnp.einsum("bhqd,bhkd->bhqk", do, vf)
        ds = p_ * (dp - delta[..., None])
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, kf)
        return dq, (jnp.einsum("bhqk,bhqd->bhkd", ds, qf),
                    jnp.einsum("bhqk,bhqd->bhkd", p_, do))

    dq, (dks, dvs) = jax.lax.scan(
        body, jnp.zeros((b, h, cq, hd), jnp.float32),
        (_chunked(k, n), _chunked(v, n), jnp.arange(n)))
    return dq * scale, _unchunked(dks), _unchunked(dvs)


def _forward(c, qt, kt, vt, q_offset, kv_len):
    """Head-major q (B,H,Sq,hd), k/v (B,H,Sk,hd) -> f32 output
    (B,H,Sq,hd) and the rows' log-sum-exp (B,H,Sq)."""
    sk = kt.shape[2]

    def one_q_chunk(qi):
        qc = jax.lax.dynamic_slice_in_dim(qt, qi * c.q_chunk, c.q_chunk,
                                          axis=2)
        lo, width, mask_fn = _kv_span(c, qi, sk, q_offset, kv_len)
        return _softmax_scan(qc, _kv_slice(kt, lo, width),
                             _kv_slice(vt, lo, width), mask_fn, c.kv_chunk)

    if c.nq == 1:
        return one_q_chunk(0)
    if c.unroll:
        outs = [one_q_chunk(i) for i in range(c.nq)]
        outs = tuple(jnp.stack(x) for x in zip(*outs))
    else:
        outs = jax.lax.map(one_q_chunk, jnp.arange(c.nq))  # (nq,B,H,Cq,...)
    return tuple(_unchunked(x) for x in outs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _attend(c, qt, kt, vt, q_offset, kv_len):
    return _forward(c, qt, kt, vt, q_offset, kv_len)[0]


def _attend_fwd(c, qt, kt, vt, q_offset, kv_len):
    out, lse = _forward(c, qt, kt, vt, q_offset, kv_len)
    return out, (qt, kt, vt, q_offset, kv_len, out, lse)


def _attend_bwd(c, res, dout):
    """Flash-style backward: per q chunk, recompute every kv chunk's
    probabilities from the saved log-sum-exp; dK/dV accumulate in f32
    across q chunks (into the chunk's window under SWA). Residuals are
    O(S*hd); nothing O(Sq*Sk) outlives one chunk pair."""
    qt, kt, vt, q_offset, kv_len, out, lse = res
    sk = kt.shape[2]
    delta = jnp.sum(dout * out, axis=-1)                    # (B,H,Sq)

    def one_q_chunk(carry, qi):
        start = qi * c.q_chunk
        qc, doc, lse_c, delta_c = (
            jax.lax.dynamic_slice_in_dim(x, start, c.q_chunk, axis=2)
            for x in (qt, dout, lse, delta))
        lo, width, mask_fn = _kv_span(c, qi, sk, q_offset, kv_len)
        dq_c, *dkv_c = _softmax_scan_bwd(
            qc, _kv_slice(kt, lo, width), _kv_slice(vt, lo, width), doc,
            lse_c, delta_c, mask_fn, c.kv_chunk)
        carry = tuple(
            acc + x if width == sk else jax.lax.dynamic_update_slice_in_dim(
                acc, _kv_slice(acc, lo, width) + x, lo, axis=2)
            for acc, x in zip(carry, dkv_c))
        return carry, dq_c

    zeros = jnp.zeros(kt.shape, jnp.float32)
    (dk, dv), dqs = jax.lax.scan(one_q_chunk, (zeros, zeros),
                                 jnp.arange(c.nq))
    return (_unchunked(dqs).astype(qt.dtype), dk.astype(kt.dtype),
            dv.astype(vt.dtype), None, None)


_attend.defvjp(_attend_fwd, _attend_bwd)


def attention_core(q, k, v, *, causal: bool, window: int | None,
                   q_offset=0, kv_len: int | None = None,
                   q_chunk: int = 512, kv_chunk: int = 512):
    """q (B,Sq,H,hd), k/v (B,Sk,H,hd) head-aligned -> (B,Sq,H,hd).

    q_offset: global position of q[0] (decode / chunked prefill).
    kv_len: actual valid kv length (<= Sk) for cache attention.
    Both are serving-only and take no gradient.
    """
    from repro.models import analysis_mode
    sq = q.shape[1]
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    unroll = analysis_mode.on()
    if unroll:
        # single-trip (full attn) / python-unrolled (SWA) so cost analysis
        # sees every chunk — see models/analysis_mode.py
        q_chunk = sq if window is None else min(2048, sq)
        kv_chunk = k.shape[1]
    q_chunk = min(q_chunk, sq)
    if sq % q_chunk:
        q_chunk = sq
    c = _Chunks(causal, window, q_chunk, kv_chunk, sq // q_chunk, unroll)
    with jax.named_scope(_SCOPE_CORE):
        if unroll:
            # autodiff through the unrolled chunks: cost analysis only
            BACKWARD_TRACES["autodiff"] += 1
            out = _forward(c, qt, kt, vt, q_offset, kv_len)[0]
        else:
            BACKWARD_TRACES["recompute"] += 1
            out = _attend(c, qt, kt, vt, q_offset, kv_len)
    return out.transpose(0, 2, 1, 3).astype(COMPUTE_DTYPE)


# --------------------------------------------------------------------------
# sequence parallelism over ctx.sp_axis (Ulysses a2a / ring attention)
# --------------------------------------------------------------------------

def ulysses_attention(q, k, v, ctx, *, causal, window):
    """DeepSpeed-Ulysses attention over the sp axis.

    q/k/v arrive sequence-sharded ``(B, S/sp, H, hd)`` (rope already
    applied with GLOBAL positions).  ONE compressed all-to-all — q, k, v
    packed along the feature dim into a single wire buffer — splits the
    head dim and concatenates the sequence dim (the transposed
    ``all_to_all_c`` layout), so the monolithic :func:`attention_core`
    runs on the full sequence with ``H/sp`` local heads; the inverse hop
    redistributes the output back.  Both hops ride the plan's ``sp``
    codec; the custom_vjp backward of a transposed a2a is exactly the
    inverse redistribute, so cotangents are compressed straight-through.
    """
    sp = ctx.sp_size()
    if sp == 1:
        return attention_core(q, k, v, causal=causal, window=window)
    h = q.shape[2]
    if h % sp:
        raise ValueError(
            f"Ulysses attention: local head count {h} not divisible by "
            f"sp axis {ctx.sp_axis!r} of size {sp}")
    qkv = jnp.concatenate([q, k, v], axis=-1)      # (B, S/sp, H, 3*hd)
    qkv = ctx.sp_all_to_all(qkv, 2, 1)             # (B, S, H/sp, 3*hd)
    qf, kf, vf = jnp.split(qkv, 3, axis=-1)
    out = attention_core(qf, kf, vf, causal=causal, window=window)
    return ctx.sp_all_to_all(out, 1, 2)            # (B, S/sp, H, hd)


def _block_bias(q_pos, kv_pos, *, causal, window):
    """Additive (Sq, Sk) mask between global q/kv position vectors."""
    m = jnp.zeros((q_pos.shape[0], kv_pos.shape[0]), jnp.float32)
    if causal:
        m = jnp.where(kv_pos[None, :] > q_pos[:, None], NEG_INF, m)
    if window is not None:
        m = jnp.where(kv_pos[None, :] <= q_pos[:, None] - window, NEG_INF, m)
    return m


def _block_partial(qf, kb, vb, bias):
    """Online-softmax partial of pre-scaled f32 q ``(B,H,Sq,hd)`` against
    one KV block ``(B,H,Sk,hd)``: returns ``(acc, m, l)``.  Safe under a
    fully-masked block (future blocks under causal masking): its partial
    is exactly ``(0, NEG_INF, 0)`` and merges as a no-op."""
    s_ = jnp.einsum("bhqd,bhkd->bhqk", qf, kb.astype(jnp.float32))
    s_ = s_ + bias[None, None]
    m = jnp.max(s_, axis=-1)
    finite = m > NEG_INF * 0.5
    msafe = jnp.where(finite, m, 0.0)
    p_ = jnp.where(finite[..., None], jnp.exp(s_ - msafe[..., None]), 0.0)
    l = jnp.sum(p_, axis=-1)
    acc = jnp.einsum("bhqk,bhkd->bhqd", p_, vb.astype(jnp.float32))
    return acc, jnp.where(finite, m, NEG_INF), l


def _merge_partial(a, b):
    """Fold two online-softmax partials (associative rescale-and-add)."""
    acc1, m1, l1 = a
    acc2, m2, l2 = b
    m = jnp.maximum(m1, m2)
    c1 = jnp.exp(m1 - m)
    c2 = jnp.exp(m2 - m)
    # both-empty: exp(0) = 1 but acc/l are exactly 0, so still a no-op
    return (acc1 * c1[..., None] + acc2 * c2[..., None], m,
            l1 * c1 + l2 * c2)


def ring_attention(q, k, v, ctx, *, causal, window):
    """Blockwise ring attention over the sp axis.

    q stays sequence-local ``(B, S/sp, H, hd)``; every peer's KV block is
    delivered by ONE compressed ppermute (k and v packed along the
    feature dim into a single wire buffer, direct-send to the peer ``t``
    ranks ahead — the two-shot idiom of the ring transports) and folded
    into an online-softmax accumulator with global-position masking.

    Hop emission is owned by :func:`repro.core.overlap.run_ring` exactly
    like the chunked AG/RS rings: the ``sp`` codec's ``schedule`` knob
    picks pipelined (barrier-fenced ticks — hop ``t-1``'s ppermute and
    block ``t-2``'s attention partial share a tick, so the softmax
    compute provably interleaves between the ppermute hops in the
    lowered HLO) or the hoisted serial baseline.  Output matches the
    monolithic core within online-softmax re-association tolerance
    (merge order is arrival order, which differs per device)."""
    sp = ctx.sp_size()
    if sp == 1:
        return attention_core(q, k, v, causal=causal, window=window)
    from repro.core import overlap

    b, s_loc, h, hd = q.shape
    i = ctx.sp_index()
    q_pos = i * s_loc + jnp.arange(s_loc)
    qf = q.transpose(0, 2, 1, 3).astype(jnp.float32) / np.sqrt(hd)
    kv = jnp.concatenate([k, v], axis=-1)          # one wire buffer per hop

    def partial_for(block, src):
        kb, vb = jnp.split(block, 2, axis=-1)
        kv_pos = src * s_loc + jnp.arange(s_loc)
        bias = _block_bias(q_pos, kv_pos, causal=causal, window=window)
        return _block_partial(qf, kb.transpose(0, 2, 1, 3),
                              vb.transpose(0, 2, 1, 3), bias)

    def transfer(t):
        perm = tuple((s, (s + t) % sp) for s in range(sp))
        return lambda blk: ctx.sp_permute(blk, perm)

    def decode(t):
        return lambda blk: partial_for(blk, (i - t) % sp)

    parts = overlap.run_ring(
        [kv] * (sp - 1),
        encode=lambda blk: blk,                    # hop = the full
        transfer=[transfer(t) for t in range(1, sp)],  # compressed ppermute
        decode=[decode(t) for t in range(1, sp)],
        schedule=overlap.ring_schedule(ctx.plan.sp))
    state = partial_for(kv, i)                     # own (diagonal) block
    for part in parts:
        state = _merge_partial(state, part)
    acc, _, l = state
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(COMPUTE_DTYPE)


def sp_attention(q, k, v, ctx, *, causal, window):
    """Dispatch the sp-axis attention flavor (``ctx.sp_mode``)."""
    if ctx.sp_mode == "ring":
        return ring_attention(q, k, v, ctx, causal=causal, window=window)
    if ctx.sp_mode != "ulysses":
        raise ValueError(f"unknown sp_mode {ctx.sp_mode!r}")
    return ulysses_attention(q, k, v, ctx, causal=causal, window=window)


# --------------------------------------------------------------------------
# full attention layer (train path)
# --------------------------------------------------------------------------

@jax.named_scope(SCOPE_ATTN)
def attention_apply(x_full, p, cfg, plan, ctx, *, causal=True,
                    window=None, positions=None, kv_source=None):
    """x_full (B, S, D) -> partial output (B, S, D) (caller reduces).

    Under an active ``ctx.sp_axis`` the sequence dim of ``x_full`` is the
    LOCAL sp shard and ``positions`` must be the shard's global positions
    (the caller offsets them); attention crosses the axis through
    :func:`sp_attention`.

    kv_source: encoder output (B, S_enc, D) for cross-attention (keys and
    values are projected from it with this layer's wk/wv, no rope)."""
    b, s, _ = x_full.shape
    hd = cfg.hd
    if positions is None:
        positions = ctx.sp_index() * s + jnp.arange(s) if ctx.sp_active \
            else jnp.arange(s)
    q = q_project(x_full, p, cfg, plan, ctx, positions)
    if kv_source is not None:
        if ctx.sp_active:
            raise NotImplementedError(
                "cross-attention under an active sp axis is not supported")
        k, v = kv_project(kv_source, p, cfg, plan, ctx, None)
    else:
        k, v = kv_project(x_full, p, cfg, plan, ctx, positions)
    k = _expand_kv(k, plan, ctx, cfg)
    v = _expand_kv(v, plan, ctx, cfg)
    if ctx.sp_active:
        out = sp_attention(q, k, v, ctx, causal=causal, window=window)
    else:
        out = attention_core(q, k, v, causal=causal, window=window)
    out = out * head_mask(plan, ctx, cfg.n_heads)[None, None, :, None]
    wo = ctx.weight_gather(p["wo"], 1)
    return out.reshape(b, s, plan.q_local * hd) @ wo


# --------------------------------------------------------------------------
# decode path (KV cache, single token)
# --------------------------------------------------------------------------

def attention_decode(x, p, cfg, plan, ctx, cache, pos):
    """x (B, 1, D) full-D; cache dict {k,v}: (B, S_cache, kv_local, hd).
    Returns (partial_out (B,1,D), new_cache). SWA uses a ring buffer of
    width ``window`` (cache S_cache == window).

    ``pos`` is either a scalar (every sequence at the same position — the
    classic fixed-batch loop) or a (B,) vector of per-slot positions (the
    continuous-batching engine, where in-flight requests sit at different
    depths).  Both paths compute bit-identical per-row results: the
    vector path's masked cache write selects exactly the values the
    scalar path's dynamic_update_slice stores."""
    b = x.shape[0]
    hd = cfg.hd
    per_slot = jnp.ndim(pos) == 1
    q, k_new, v_new = qkv_project(
        x, p, cfg, plan, ctx,
        positions=pos[:, None] if per_slot else jnp.full((1,), pos))
    s_cache = cache["k"].shape[1]
    slot = pos % s_cache if cfg.window is not None else pos
    if per_slot:
        # each batch row writes its own cache position: masked write over
        # the length axis (O(S) select, value-identical to the slice
        # update the scalar path performs)
        hit = jnp.arange(s_cache)[None, :] == slot[:, None]   # (B, S)
        wr = hit[:, :, None, None]
        k = jnp.where(wr, k_new.astype(cache["k"].dtype), cache["k"])
        v = jnp.where(wr, v_new.astype(cache["v"].dtype), cache["v"])
    else:
        k = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k_new.astype(cache["k"].dtype), slot, axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v_new.astype(cache["v"].dtype), slot, axis=1)
    new_cache = {"k": k, "v": v}
    ke = _expand_kv(k, plan, ctx, cfg)
    ve = _expand_kv(v, plan, ctx, cfg)
    # single-token attention: direct softmax over the cache. attn_f32=False
    # (hillclimb variant) keeps the cache reads in bf16 and only promotes
    # the (tiny) score/prob tensors.
    acc_t = jnp.float32 if plan.attn_f32 else ke.dtype
    scale = 1.0 / np.sqrt(hd)
    qf = q.astype(acc_t) * scale                           # (B,1,H,hd)
    scores = jnp.einsum("bqhd,bshd->bhqs", qf,
                        ke.astype(acc_t)).astype(jnp.float32)
    kv_pos = jnp.arange(s_cache)[None, :]                  # (1, S)
    pos_c = pos[:, None] if per_slot else \
        jnp.reshape(jnp.asarray(pos), (1, 1))              # (B|1, 1)
    if cfg.window is not None:
        # ring buffer: slot j holds position pos - ((pos - j) mod W);
        # valid iff that position has been written (>= 0)
        age = jnp.mod(pos_c - kv_pos, s_cache)
        valid = age <= pos_c
    else:
        valid = kv_pos <= pos_c
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqs,bshd->bqhd", probs.astype(acc_t),
                     ve.astype(acc_t))
    out = out.astype(COMPUTE_DTYPE)
    out = out * head_mask(plan, ctx, cfg.n_heads)[None, None, :, None]
    wo = ctx.weight_gather(p["wo"], 1)
    return out.reshape(b, 1, plan.q_local * hd) @ wo, new_cache
