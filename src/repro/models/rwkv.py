"""RWKV6 "Finch" block (attention-free, data-dependent decay).

TP sharding: the 32 heads (d_model/64) shard cleanly over the model axis;
the residual stream stays sequence-parallel, so the block has exactly the
same compressed gather/scatter TP communication sites as dense attention
(DESIGN.md §4: attention-free != TP-communication-free).

Time-mix recurrence (per head, state S in R^{ck x cv}):
    S_t = diag(w_t) S_{t-1} + k_t v_t^T
    o_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T)

Computed in chunks: intra-chunk pair scores use the *bounded* decay ratio
exp(logA_{t-1} - logA_j) <= 1 evaluated jointly (never the unbounded
k/A_j factorization), inter-chunk via the carried state. lax.scan over
chunks => O(S) work, O(1) decode state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import COMPUTE_DTYPE

LORA_MIX = 32
LORA_W = 64
N_STREAMS = 5  # w, k, v, r, g


def rwkv_specs(pb, name: str, cfg, plan):
    d, f = cfg.d_model, cfg.d_ff
    # time-mix
    pb.add(f"{name}.tm.mu_x", (d,), init="zeros")
    pb.add(f"{name}.tm.mu", (N_STREAMS, d), init="zeros")
    pb.add(f"{name}.tm.lora_a", (d, N_STREAMS * LORA_MIX), scale=0.01)
    pb.add(f"{name}.tm.lora_b", (N_STREAMS, LORA_MIX, d), init="zeros")
    pb.add(f"{name}.tm.w0", (d,), tp_dim=0, init="zeros")
    pb.add(f"{name}.tm.wa", (d, LORA_W), scale=0.01)
    pb.add(f"{name}.tm.wb", (LORA_W, d), tp_dim=1, init="zeros")
    pb.add(f"{name}.tm.u", (d,), tp_dim=0, init="zeros")
    pb.add(f"{name}.tm.wr", (d, d), fsdp_dim=0, tp_dim=1)
    pb.add(f"{name}.tm.wk", (d, d), fsdp_dim=0, tp_dim=1)
    pb.add(f"{name}.tm.wv", (d, d), fsdp_dim=0, tp_dim=1)
    pb.add(f"{name}.tm.wg", (d, d), fsdp_dim=0, tp_dim=1)
    pb.add(f"{name}.tm.wo", (d, d), fsdp_dim=1, tp_dim=0)
    pb.add(f"{name}.tm.ln_scale", (d,), tp_dim=0, init="zeros")
    pb.add(f"{name}.tm.ln_bias", (d,), tp_dim=0, init="zeros")
    # channel-mix
    pb.add(f"{name}.cm.mu_k", (d,), init="zeros")
    pb.add(f"{name}.cm.mu_r", (d,), init="zeros")
    pb.add(f"{name}.cm.wk", (d, f), fsdp_dim=0, tp_dim=1)
    pb.add(f"{name}.cm.wv", (f, d), fsdp_dim=1, tp_dim=0)
    pb.add(f"{name}.cm.wr", (d, d), fsdp_dim=0)  # gate needs full D: replicated over tp


def _token_shift(x, prev):
    """x (B,S,D); prev (B,1,D) last token of previous segment (zeros at BOS)."""
    return jnp.concatenate([prev, x[:, :-1]], axis=1)


def _mix_streams(x, xx, p):
    sx = xx - x
    xxx = x + sx * p["mu_x"].astype(x.dtype)
    lo = jnp.tanh(xxx @ p["lora_a"])                        # (B,S,5*r)
    b, s, _ = lo.shape
    lo = lo.reshape(b, s, N_STREAMS, LORA_MIX)
    delta = jnp.einsum("bsnr,nrd->bsnd", lo, p["lora_b"])
    mixed = x[:, :, None] + sx[:, :, None] * (
        p["mu"].astype(x.dtype)[None, None] + delta.astype(x.dtype))
    return [mixed[:, :, i] for i in range(N_STREAMS)]       # w,k,v,r,g


def _heads(x, hd):
    b, s, d = x.shape
    return x.reshape(b, s, d // hd, hd)


def _group_norm(o, scale, bias, eps=64e-5):
    """Per-head normalization (RWKV ln_x). o (B,S,H,hd)."""
    of = o.astype(jnp.float32)
    mu = jnp.mean(of, axis=-1, keepdims=True)
    var = jnp.var(of, axis=-1, keepdims=True)
    out = (of - mu) * jax.lax.rsqrt(var + eps)
    b, s, h, hd = o.shape
    out = out * (1.0 + scale.astype(jnp.float32).reshape(h, hd))
    out = out + bias.astype(jnp.float32).reshape(h, hd)
    return out.astype(o.dtype)


def _chunk_recurrence(r, k, v, logw, u, s0, chunk: int):
    """r,k,v (B,S,H,c); logw (B,S,H,c) = log decay; u (H,c); s0 (B,H,c,c).
    Returns (o (B,S,H,c), s_final)."""
    b, s, h, c = r.shape
    chunk = min(chunk, s)
    if s % chunk:
        chunk = s
    n = s // chunk

    rs = r.reshape(b, n, chunk, h, c).transpose(1, 0, 2, 3, 4)
    ks = k.reshape(b, n, chunk, h, c).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(b, n, chunk, h, c).transpose(1, 0, 2, 3, 4)
    lw = logw.reshape(b, n, chunk, h, c).transpose(1, 0, 2, 3, 4)

    def body(s_in, inp):
        rc, kc, vc, lwc = (t.astype(jnp.float32) for t in inp)
        la = jnp.cumsum(lwc, axis=1)                        # logA_t (B,C,H,c)
        la_prev = la - lwc                                  # logA_{t-1}
        # intra-chunk: bounded ratio exp(logA_{t-1} - logA_j), j < t
        ratio = jnp.exp(jnp.clip(
            la_prev[:, :, None] - la[:, None, :], -60.0, 0.0))  # (B,t,j,H,c)
        scores = jnp.einsum("bthc,bjhc,btjhc->bhtj", rc, kc, ratio)
        tri = jnp.tril(jnp.ones((chunk, chunk), jnp.float32), -1)
        scores = scores * tri[None, None]
        diag = jnp.einsum("bthc,hc,bthc->bht", rc, u.astype(jnp.float32), kc)
        scores = scores + jnp.eye(chunk, dtype=jnp.float32)[None, None] \
            * diag[..., None]
        o_intra = jnp.einsum("bhtj,bjhc->bthc", scores, vc)
        # inter-chunk: o += (r .* exp(logA_{t-1}))^T S_0
        r_dec = rc * jnp.exp(jnp.clip(la_prev, -60.0, 0.0))
        o_inter = jnp.einsum("bthc,bhcv->bthv", r_dec, s_in)
        # state update: S = diag(A_C) S_0 + sum_j (k_j .* A_C/A_j) v_j^T
        a_end = la[:, -1]                                   # (B,H,c)
        k_dec = kc * jnp.exp(jnp.clip(a_end[:, None] - la, -60.0, 0.0))
        s_out = jnp.exp(jnp.clip(a_end, -60.0, 0.0))[..., None] * s_in \
            + jnp.einsum("bjhc,bjhv->bhcv", k_dec, vc)
        return s_out, (o_intra + o_inter).astype(COMPUTE_DTYPE)

    # NOTE (analysis mode): the chunk scan body is counted once by XLA
    # cost analysis, under-counting the intra-chunk recurrence by
    # (n-1)/n. The recurrence is ~1-2% of layer flops (the 6*D^2 stream
    # matmuls dominate), so the roofline impact is negligible and we keep
    # the scan — unrolling 512 chunk bodies made prefill_32k lowering
    # pathologically slow.
    s_fin, os_ = jax.lax.scan(body, s0.astype(jnp.float32),
                              (rs, ks, vs, lw))
    o = os_.transpose(1, 0, 2, 3, 4).reshape(b, s, h, c)
    return o, s_fin


def time_mix_apply(x_full, p, cfg, plan, ctx, *, state=None, chunk=64):
    """x_full (B,S,D) -> (partial out (B,S,D), new_state).

    state (decode): dict {shift (B,1,D), s (B,H_loc,c,c)} or None (train,
    zeros)."""
    b, s, d = x_full.shape
    hd = cfg.hd
    h_loc = plan.q_local
    tm = p["tm"]
    prev = state["shift"] if state is not None else jnp.zeros(
        (b, 1, d), x_full.dtype)
    xx = _token_shift(x_full, prev) if s > 1 else prev
    xw, xk, xv, xr, xg = _mix_streams(x_full, xx, tm)

    wr = ctx.weight_gather(tm["wr"], 0)
    wk = ctx.weight_gather(tm["wk"], 0)
    wv = ctx.weight_gather(tm["wv"], 0)
    wg = ctx.weight_gather(tm["wg"], 0)
    r = _heads(xr @ wr, hd)                                # (B,S,Hl,hd)
    k = _heads(xk @ wk, hd)
    v = _heads(xv @ wv, hd)
    g = jax.nn.silu(xg @ wg)

    w_lin = tm["w0"].astype(jnp.float32) + \
        jnp.tanh(xw @ tm["wa"]).astype(jnp.float32) @ tm["wb"].astype(jnp.float32)
    logw = -jnp.exp(jnp.clip(w_lin, -20.0, 10.0))          # log decay < 0
    logw = _heads(logw, hd)
    u = tm["u"].reshape(h_loc, hd)

    s0 = state["s"] if state is not None else jnp.zeros(
        (b, h_loc, hd, hd), jnp.float32)
    if s == 1:
        # decode: direct single-step recurrence
        rf, kf, vf = (t[:, 0].astype(jnp.float32) for t in (r, k, v))
        lwf = logw[:, 0].astype(jnp.float32)
        kv = jnp.einsum("bhc,bhv->bhcv", kf, vf)
        o = jnp.einsum("bhc,bhcv->bhv", rf, s0
                       + u.astype(jnp.float32)[None, :, :, None] * kv)
        s_new = jnp.exp(lwf)[..., None] * s0 + kv
        o = o[:, None].reshape(b, 1, h_loc, hd).astype(COMPUTE_DTYPE)
    else:
        o, s_new = _chunk_recurrence(r, k, v, logw, u, s0, chunk)
    o = _group_norm(o, tm["ln_scale"], tm["ln_bias"])
    o = (o.reshape(b, s, h_loc * hd) * g).astype(COMPUTE_DTYPE)
    wo = ctx.weight_gather(tm["wo"], 1)
    out = o @ wo                                           # tp-partial
    new_state = {"shift": x_full[:, -1:], "s": s_new}
    return out, new_state


def channel_mix_apply(x_full, p, cfg, plan, ctx, *, state=None):
    """x_full (B,S,D) -> (partial out (B,S,D), new_state {shift})."""
    b, s, d = x_full.shape
    cm = p["cm"]
    prev = state["shift"] if state is not None else jnp.zeros(
        (b, 1, d), x_full.dtype)
    xx = _token_shift(x_full, prev) if s > 1 else prev
    xk = x_full + (xx - x_full) * cm["mu_k"].astype(x_full.dtype)
    xr = x_full + (xx - x_full) * cm["mu_r"].astype(x_full.dtype)
    wk = ctx.weight_gather(cm["wk"], 0)
    wv = ctx.weight_gather(cm["wv"], 1)
    wr = ctx.weight_gather(cm["wr"], 0)
    k = jnp.square(jax.nn.relu(xk @ wk))
    r = jax.nn.sigmoid(xr @ wr)                            # full D (replicated W)
    out = r * (k @ wv)                                     # gate distributes over psum
    return out, {"shift": x_full[:, -1:]}
