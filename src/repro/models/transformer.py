"""Transformer assembly: blocks per family, segmented layer scans, full
train forward for every assigned architecture (decode lives in
``repro/serve/serve_step.py``).

Layer stacking: params are stacked (L, ...) per *segment* — a maximal run
of layers with identical static structure (e.g. hymba's full-attention
layers 0/15/31 split its 32 layers into 5 segments of 2 body types) — and
executed with lax.scan for O(1) compile scaling in depth (MaxText-style).

The residual stream is sequence-sharded over the model axis (SP mode,
default) or replicated (AllReduce mode); all TP communication goes through
the ParallelCtx compressed collectives.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro import compat
from repro.core.telemetry import SCOPE_HEAD
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (COMPUTE_DTYPE, ParamBuilder, apply_norm,
                                 embed_specs, mlp_apply, mlp_specs,
                                 norm_specs, sinusoid_pos,
                                 vocab_parallel_xent)

ZERO = lambda: jnp.zeros((), jnp.float32)  # noqa: E731


# --------------------------------------------------------------------------
# segments
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Segment:
    kind: str      # "full" | "swa"  (attention flavor within the family)
    start: int
    count: int


def layer_segments(cfg) -> list[Segment]:
    n = cfg.n_layers
    if cfg.family == "hybrid" and cfg.hybrid_full_attn:
        segs, cur = [], 0
        fulls = set(cfg.hybrid_full_attn)
        while cur < n:
            kind = "full" if cur in fulls else "swa"
            end = cur
            while end < n and (("full" if end in fulls else "swa") == kind):
                end += 1
            segs.append(Segment(kind, cur, end - cur))
            cur = end
        return segs
    kind = "swa" if cfg.window is not None else "full"
    return [Segment(kind, 0, n)]


# --------------------------------------------------------------------------
# per-layer specs
# --------------------------------------------------------------------------

def block_specs(cfg, plan, *, cross: bool = False) -> dict:
    pb = ParamBuilder()
    d = cfg.d_model
    norm_specs(pb, "norm1", d, cfg.norm)
    norm_specs(pb, "norm2", d, cfg.norm)
    if cfg.family == "rwkv":
        rwkv_mod.rwkv_specs(pb, "blk", cfg, plan)
        specs = pb.specs
        specs.update(specs.pop("blk"))
        return specs
    attn_mod.attn_specs(pb, "attn", cfg, plan)
    if cross:
        norm_specs(pb, "norm_x", d, cfg.norm)
        attn_mod.attn_specs(pb, "xattn", cfg, plan)
    if cfg.family == "moe":
        moe_mod.moe_specs(pb, "moe", cfg, plan)
    else:
        mlp_specs(pb, "mlp", d, cfg.d_ff, cfg.mlp)
    if cfg.family == "hybrid":
        ssm_mod.ssm_specs(pb, "ssm", cfg, plan)
        pb.add("branch_gate", (2,), init="zeros")  # learned attn/ssm balance
    return pb.specs


# --------------------------------------------------------------------------
# residual-stream TP helpers (SP vs AllReduce mode)
# --------------------------------------------------------------------------

def tp_enter(x_shard, ctx):
    """seq-sharded residual -> full-seq activations (TACO site: AllGather)."""
    if ctx.tp_mode == "sp":
        return ctx.sp_gather(x_shard, 1)
    return ctx.tp_f(x_shard)


def tp_exit(y_partial, ctx):
    """tp-partial block output -> seq-sharded residual (TACO site: RS)."""
    if ctx.tp_mode == "sp":
        return ctx.sp_scatter(y_partial, 1)
    return ctx.tp_g(y_partial)


def seq_slice(x_full, ctx, tp: int):
    """Full-seq (replicated) -> this device's seq shard, no comm."""
    if ctx.tp_mode != "sp" or tp == 1:
        return x_full
    s_loc = x_full.shape[1] // tp
    idx = jax.lax.axis_index(ctx.tp_axis)
    return jax.lax.dynamic_slice_in_dim(x_full, idx * s_loc, s_loc, axis=1)


# --------------------------------------------------------------------------
# block forward (train path; full sequence)
# --------------------------------------------------------------------------

def block_apply(x_shard, lp, enc_kv, cfg, plan, ctx, *, attn_kind: str,
                positions, causal=True):
    """One transformer block on the seq-sharded residual stream.
    enc_kv: encoder output (B, S_enc, D) or None."""
    window = cfg.window if attn_kind == "swa" else None

    if cfg.family == "rwkv":
        h = apply_norm(x_shard, lp["norm1"], cfg.norm, cfg.norm_eps)
        h_full = tp_enter(h, ctx)
        out, _ = rwkv_mod.time_mix_apply(h_full, lp, cfg, plan, ctx)
        x_shard = x_shard + tp_exit(out, ctx)
        h = apply_norm(x_shard, lp["norm2"], cfg.norm, cfg.norm_eps)
        h_full = tp_enter(h, ctx)
        out, _ = rwkv_mod.channel_mix_apply(h_full, lp, cfg, plan, ctx)
        return x_shard + tp_exit(out, ctx), ZERO()

    # ---- mixer (attention / attention+ssm)
    h = apply_norm(x_shard, lp["norm1"], cfg.norm, cfg.norm_eps)
    h_full = tp_enter(h, ctx)
    partial = attn_mod.attention_apply(
        h_full, lp["attn"], cfg, plan, ctx,
        causal=causal, window=window, positions=positions)
    if cfg.family == "hybrid":
        ssm_out, _ = ssm_mod.ssm_apply(h_full, lp["ssm"], cfg, plan, ctx)
        gates = (jax.nn.sigmoid(lp["branch_gate"].astype(jnp.float32))
                 ).astype(COMPUTE_DTYPE)
        partial = partial * gates[0] + ssm_out * gates[1]
    x_shard = x_shard + tp_exit(partial, ctx)

    # ---- cross-attention (whisper decoder)
    if enc_kv is not None:
        h = apply_norm(x_shard, lp["norm_x"], cfg.norm, cfg.norm_eps)
        h_full = tp_enter(h, ctx)
        partial = attn_mod.attention_apply(
            h_full, lp["xattn"], cfg, plan, ctx,
            causal=False, window=None, positions=positions,
            kv_source=enc_kv)
        x_shard = x_shard + tp_exit(partial, ctx)

    # ---- mlp / moe
    h = apply_norm(x_shard, lp["norm2"], cfg.norm, cfg.norm_eps)
    h_full = tp_enter(h, ctx)
    aux = ZERO()
    if cfg.family == "moe":
        partial, aux = moe_mod.moe_apply(h_full, lp["moe"], cfg, plan, ctx)
        aux = aux.astype(jnp.float32)
    else:
        partial = mlp_apply(h_full, lp["mlp"], cfg.mlp, ctx)
    out = tp_exit(partial, ctx)
    if cfg.mlp == "gelu":
        out = out + lp["mlp"]["b2"].astype(out.dtype)
    return x_shard + out, aux


def run_segments(x_shard, seg_params, segments, cfg, plan, ctx, *,
                 positions, enc_kv=None, causal=True):
    """Scan each segment's stacked layers. Returns (x_shard, aux_sum).

    Per-layer CommPlan overrides (``skip_first``/``skip_last``) are
    resolved here at trace time: ``ctx.layer_views`` splits each segment
    into static contiguous spans of layers sharing one plan, each span
    scanned with its own ParallelCtx view.  With no overrides the split is
    the whole segment with ``ctx`` itself — byte-identical jit keys."""
    from repro.core.parallel import iter_layer_spans
    aux_total = ZERO()
    enc_arg = enc_kv if enc_kv is not None else ZERO()  # scan-friendly dummy
    n_total = max(s.start + s.count for s in segments)

    for seg, sp_ in zip(segments, seg_params):
        for span_n, span_ctx, sp_span in iter_layer_spans(
                ctx, seg.start, seg.count, n_total, sp_):

            def blk(x, lp, ek, kind=seg.kind, c=span_ctx):
                return block_apply(x, lp, ek if enc_kv is not None else None,
                                   cfg, plan, c, attn_kind=kind,
                                   positions=positions, causal=causal)

            if plan.remat and plan.remat_policy != "none":
                pol = (jax.checkpoint_policies.nothing_saveable
                       if plan.remat_policy == "full" else
                       jax.checkpoint_policies
                       .dots_with_no_batch_dims_saveable)
                fn = jax.checkpoint(blk, policy=pol)
            else:
                fn = blk

            if plan.scan_layers:
                def body(carry, lp, fn=fn):
                    x, aux = carry
                    x, a = fn(x, lp, enc_arg)
                    return (x, aux + a), None

                (x_shard, aux_total), _ = jax.lax.scan(
                    body, (x_shard, aux_total), sp_span)
            else:
                # unrolled (dry-run roofline mode): XLA's cost analysis
                # counts a scan body ONCE, hiding (L-1)/L of the flops/
                # bytes/collectives — unrolling makes the compiled artifact
                # reflect the true per-step cost.
                for i in range(span_n):
                    lp_i = compat.tree_map(lambda a: a[i], sp_span)
                    x_shard, a = fn(x_shard, lp_i, enc_arg)
                    aux_total = aux_total + a
    return x_shard, aux_total


# --------------------------------------------------------------------------
# whole-model specs
# --------------------------------------------------------------------------

def model_specs(cfg, plan) -> dict:
    pb = ParamBuilder()
    embed_specs(pb, plan.vocab_pad, cfg.d_model, cfg.tie_embeddings)
    if cfg.pos == "learned":
        pb.add("pos_embed", (8192, cfg.d_model), fsdp_dim=0, scale=0.01)
    norm_specs(pb, "final_norm", cfg.d_model, cfg.norm)
    specs = pb.specs

    per_layer = block_specs(cfg, plan, cross=(cfg.family == "encdec"))
    specs["segments"] = [
        ParamBuilder.stack(per_layer, seg.count) for seg in layer_segments(cfg)
    ]
    if cfg.family == "encdec":
        enc_layer = block_specs(cfg, plan, cross=False)
        specs["enc_segments"] = [ParamBuilder.stack(enc_layer, cfg.enc_layers)]
        pb2 = ParamBuilder()
        norm_specs(pb2, "enc_final_norm", cfg.d_model, cfg.norm)
        specs.update(pb2.specs)
    return specs


# --------------------------------------------------------------------------
# train forward (loss)
# --------------------------------------------------------------------------

def head_table(params, cfg):
    return params["embed"]["table"] if cfg.tie_embeddings \
        else params["head"]["table"]


def add_positional(x_shard, params, cfg, ctx, seq: int):
    """Learned/sinusoid absolute positions, added on the seq shard."""
    if cfg.pos not in ("learned", "sinusoid"):
        return x_shard
    s_loc = x_shard.shape[1]
    if ctx.tp_mode == "sp":
        idx = jax.lax.axis_index(ctx.tp_axis)
        start = idx * s_loc
    else:
        start = 0
    if ctx.sp_active:
        # seq is the sp-LOCAL shard length; offset to global positions
        start = start + ctx.sp_index() * seq
    if cfg.pos == "learned":
        table = ctx.weight_gather(params["pos_embed"], 0)
        pe = jax.lax.dynamic_slice_in_dim(table, start, s_loc, axis=0)
    else:
        pe = sinusoid_pos(seq * ctx.sp_size(), cfg.d_model)
        pe = jax.lax.dynamic_slice_in_dim(pe, start, s_loc, axis=0)
    return x_shard + pe[None].astype(x_shard.dtype)


def embed_partial(tokens, table_local, ctx):
    """Vocab-parallel lookup -> tp-partial (B, S, D) (pre-reduction)."""
    v_loc = table_local.shape[0]
    table = ctx.weight_gather(table_local, 1)
    idx = jax.lax.axis_index(ctx.tp_axis)
    shifted = tokens - idx * v_loc
    valid = (shifted >= 0) & (shifted < v_loc)
    part = jnp.take(table, jnp.clip(shifted, 0, v_loc - 1), axis=0)
    return jnp.where(valid[..., None], part, 0).astype(COMPUTE_DTYPE)


def encoder_forward(params, frames, cfg, plan, ctx):
    """Whisper encoder: frames (B, S_enc, D) stub embeddings -> enc_out
    (B, S_enc, D) full-seq (for the decoder's cross-attention)."""
    s_enc = frames.shape[1]
    x = seq_slice(frames.astype(COMPUTE_DTYPE), ctx, plan.tp)
    x = add_positional(x, params, cfg, ctx, s_enc)
    x, _ = run_segments(x, params["enc_segments"],
                        [Segment("full", 0, cfg.enc_layers)],
                        cfg, plan, ctx,
                        positions=jnp.arange(s_enc), causal=False)
    x = apply_norm(x, params["enc_final_norm"], cfg.norm, cfg.norm_eps)
    return tp_enter(x, ctx)                              # TACO gather site


def forward_train(params, batch, cfg, plan, ctx):
    """batch: tokens (B,S_t) int32, labels (B,S_t), mask (B,S_t) plus
    optional 'patches' (B,T_f,D) / 'frames' (B,S_enc,D) stubs.
    Returns (loss_sum, token_count, aux) — caller psums over dp."""
    tokens, labels, mask = batch["tokens"], batch["labels"], batch["mask"]

    enc_kv = None
    if cfg.family == "encdec":
        enc_kv = encoder_forward(params, batch["frames"], cfg, plan, ctx)

    # ---- embedding (vocab-parallel; TACO reduce-scatter site)
    with jax.named_scope(SCOPE_HEAD):
        if cfg.frontend == "patches":
            patches = batch["patches"].astype(COMPUTE_DTYPE)
            idx = jax.lax.axis_index(ctx.tp_axis)
            pat = jnp.where(idx == 0, patches, jnp.zeros_like(patches))
            emb = embed_partial(tokens, params["embed"]["table"], ctx)
            partial = jnp.concatenate([pat, emb], axis=1)
            labels = jnp.concatenate(
                [jnp.zeros(pat.shape[:2], labels.dtype), labels], axis=1)
            mask = jnp.concatenate(
                [jnp.zeros(pat.shape[:2], mask.dtype), mask], axis=1)
        else:
            partial = embed_partial(tokens, params["embed"]["table"], ctx)
        seq = partial.shape[1]
        x = tp_exit(partial, ctx)
        x = add_positional(x, params, cfg, ctx, seq)

    positions = jnp.arange(seq)
    if ctx.sp_active:
        positions = positions + ctx.sp_index() * seq
    x, aux = run_segments(x, params["segments"], layer_segments(cfg),
                          cfg, plan, ctx,
                          positions=positions, enc_kv=enc_kv,
                          causal=True)
    with jax.named_scope(SCOPE_HEAD):
        x = apply_norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
        x_full = tp_enter(x, ctx)                         # TACO gather site
        loss_sum, count = vocab_parallel_xent(
            x_full, head_table(params, cfg), labels, mask, ctx, plan)
    return loss_sum, count, aux
