"""Fused ASH-decompress Pallas TPU kernels — paper §4.1 "fused_ash_decompress".

Two kernels:

* ``decompress_blocks_pallas`` — dequantize + inverse rotation + inverse
  rescale in one VMEM-resident pass (receiver side of AllGather).

* ``decompress_reduce_pallas`` — the ReduceScatter local reduction, fused
  *in the rotated domain* (beyond-paper, DESIGN.md §7.2): because the
  Hadamard rotation is linear,
      sum_p H^-1(q_p s_p)/alpha_p  ==  H^-1( sum_p q_p (s_p/alpha_p) )
  so P peer contributions cost ONE inverse rotation instead of P. The
  accumulation itself is a fp8-dequant + fused-multiply-add on the VPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import ash as ash_mod
# ROW_TILE is shared with the compress kernels: _row_tiles sizes its spans
# by it, and the tile-shape bit-parity contract (see ash_compress.
# _row_tiles) requires every kernel to matmul at the same (ROW_TILE, B)
from repro.kernels.ash_compress import (ROW_TILE, _pad_rows, _row_tiles,
                                        rotate, wire_geometry)


def _expand_scale(s, r, b, groups):
    return jnp.repeat(s, b // groups, axis=-1).reshape(r, b)


def _decompress_kernel(q_ref, s_ref, alpha_ref, h_ref, o_ref, *, groups,
                       apply_rotation, out_dtype):
    q = q_ref[...].astype(jnp.float32)                      # (R, B)
    r, b = q.shape
    z = q * _expand_scale(s_ref[...], r, b, groups)
    if apply_rotation:
        g = rotate(z, h_ref[...])
    else:
        g = z
    g = g / alpha_ref[...]
    o_ref[...] = g.astype(out_dtype)


def decompress_blocks_pallas(q, s, alpha, cfg, interpret: bool = False):
    """(q (M,B), s (M,G), alpha (M,)|None) -> blocks (M,B) compute dtype.

    Like ``compress_blocks_pallas``, not jit-wrapped: call sites already
    sit under an outer jit (nested jit = pure dispatch overhead)."""
    fmt = cfg.format_spec
    m, b = q.shape
    groups = s.shape[-1]
    if alpha is None:  # folded metadata: scale already carries s/alpha
        alpha = jnp.ones((m,), jnp.float32)
    mp = ((m + ROW_TILE - 1) // ROW_TILE) * ROW_TILE
    if mp != m:
        q = jnp.pad(q, ((0, mp - m), (0, 0)))
        s = jnp.pad(s, ((0, mp - m), (0, 0)))
        alpha = jnp.pad(alpha, (0, mp - m), constant_values=1.0)
    h = ash_mod.hadamard_matrix(b, jnp.float32)
    kernel = functools.partial(
        _decompress_kernel, groups=groups,
        apply_rotation=cfg.transform in ("ash", "hadamard"),
        out_dtype=cfg.compute_dtype)
    out = pl.pallas_call(
        kernel,
        grid=(mp // ROW_TILE,),
        in_specs=[
            pl.BlockSpec((ROW_TILE, b), lambda i: (i, 0)),
            pl.BlockSpec((ROW_TILE, groups), lambda i: (i, 0)),
            pl.BlockSpec((ROW_TILE, 1), lambda i: (i, 0)),
            pl.BlockSpec((b, b), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((ROW_TILE, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, b), cfg.compute_dtype),
        interpret=interpret,
    )(q, s, alpha.reshape(mp, 1), h)
    return out[:m] if mp != m else out


def _decompress_reduce_kernel(q_ref, f_ref, h_ref, o_ref, *, groups,
                              apply_rotation, out_dtype):
    q = q_ref[...].astype(jnp.float32)                      # (P, R, B)
    p, r, b = q.shape
    f = f_ref[...]                                          # (P, R, G) = s/alpha
    fe = jnp.repeat(f, b // groups, axis=-1).reshape(p, r, b)
    acc = jnp.sum(q * fe, axis=0)                           # rotated-domain sum
    if apply_rotation:
        acc = rotate(acc, h_ref[...])                       # ONE inverse rotation
    o_ref[...] = acc.astype(out_dtype)


def decompress_reduce_pallas(q, s, alpha, cfg, interpret: bool = False):
    """Stacked peers: q (P,M,B), s (P,M,G), alpha (P,M)|None -> sum (M,B).
    Not jit-wrapped (see ``decompress_blocks_pallas``)."""
    peers, m, b = q.shape
    groups = s.shape[-1]
    f = s if alpha is None else s / alpha[..., None]
    mp = ((m + ROW_TILE - 1) // ROW_TILE) * ROW_TILE
    if mp != m:
        q = jnp.pad(q, ((0, 0), (0, mp - m), (0, 0)))
        f = jnp.pad(f, ((0, 0), (0, mp - m), (0, 0)))
    h = ash_mod.hadamard_matrix(b, jnp.float32)
    kernel = functools.partial(
        _decompress_reduce_kernel, groups=groups,
        apply_rotation=cfg.transform in ("ash", "hadamard"),
        out_dtype=cfg.compute_dtype)
    out = pl.pallas_call(
        kernel,
        grid=(mp // ROW_TILE,),
        in_specs=[
            pl.BlockSpec((peers, ROW_TILE, b), lambda i: (0, i, 0)),
            pl.BlockSpec((peers, ROW_TILE, groups), lambda i: (0, i, 0)),
            pl.BlockSpec((b, b), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((ROW_TILE, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, b), cfg.compute_dtype),
        interpret=interpret,
    )(q, f, h)
    return out[:m] if mp != m else out


# --------------------------------------------------------------------------
# fused wire consumption: the receiver-side duals of
# ash_compress.compress_wire_pallas — dequantize straight out of the packed
# uint8 wire buffer by bitcasting its static wire_layout(n) byte ranges in
# VMEM (no unpack_wire slice-and-bitcast copies between the collective and
# the kernel).
# --------------------------------------------------------------------------

def _wire_fields(w, n, mb, b, groups, folded, payload_dtype):
    """Bitcast the payload/scale/alpha byte ranges of wire rows ``w``
    (..., total_bytes) back to typed arrays — the in-kernel mirror of
    ``unpack_wire``."""
    lead = w.shape[:-1]
    q = jax.lax.bitcast_convert_type(
        w[..., :n].reshape(*lead, mb, b), payload_dtype)
    s = jax.lax.bitcast_convert_type(
        w[..., n:n + mb * groups * 4].reshape(*lead, mb, groups, 4),
        jnp.float32)
    if folded:
        return q, s, None
    alpha = jax.lax.bitcast_convert_type(
        w[..., n + mb * groups * 4:].reshape(*lead, mb, 4), jnp.float32)
    return q, s, alpha


def _decompress_wire_kernel(w_ref, h_ref, o_ref, *, mb, b, groups, folded,
                            payload_dtype, apply_rotation, out_dtype):
    n = mb * b
    q, s, alpha = _wire_fields(w_ref[...][0], n, mb, b, groups, folded,
                               payload_dtype)
    # ROW_TILE-shaped tiles for bit-parity with decompress_blocks_pallas
    # (see _row_tiles's gemv note); partial tiles pad alpha with 1s so the
    # discarded rows stay finite
    for r0, rows in _row_tiles(mb):
        qt = _pad_rows(q[r0:r0 + rows].astype(jnp.float32), ROW_TILE)
        st = _pad_rows(s[r0:r0 + rows].reshape(rows, groups), ROW_TILE)
        z = qt * _expand_scale(st, ROW_TILE, b, groups)
        g = rotate(z, h_ref[...]) if apply_rotation else z
        if not folded:   # folded metadata already carries s/alpha
            at = _pad_rows(alpha[r0:r0 + rows], ROW_TILE, value=1.0)
            g = g / at[:, None]
        o_ref[0, r0 * b:r0 * b + rows * b] = \
            g[:rows].reshape(rows * b).astype(out_dtype)


def decompress_wire_pallas(wire: jax.Array, n: int, cfg,
                           interpret: bool = False):
    """(slots, total_bytes) packed uint8 -> (slots, n) compute dtype.

    One grid step per slot, reading the slot's wire row once from HBM.
    Bit-identical to ``decode(unpack_wire(wire, layout), n, dtype)`` on the
    same impl (shared row-wise math; see _block_compress's contract note).
    Not jit-wrapped: call sites always sit under an outer jit."""
    fmt = cfg.format_spec
    slots, total = wire.shape
    b = cfg.block_size
    mb, groups, _, _, want = wire_geometry(cfg, n)
    if total != want:
        raise ValueError(f"wire row has {total} bytes, layout for n={n} "
                         f"declares {want}")
    h = ash_mod.hadamard_matrix(b, jnp.float32)
    kernel = functools.partial(
        _decompress_wire_kernel, mb=mb, b=b, groups=groups,
        folded=(cfg.metadata == "folded"),
        payload_dtype=fmt.dtype if fmt.is_float else jnp.int8,
        apply_rotation=cfg.transform in ("ash", "hadamard"),
        out_dtype=cfg.compute_dtype)
    return pl.pallas_call(
        kernel,
        grid=(slots,),
        in_specs=[
            pl.BlockSpec((1, total), lambda i: (i, 0)),
            pl.BlockSpec((b, b), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((slots, n), cfg.compute_dtype),
        interpret=interpret,
    )(wire, h)


def _decompress_reduce_wire_kernel(w_ref, h_ref, o_ref, *, mb, b, groups,
                                   folded, payload_dtype, apply_rotation,
                                   out_dtype):
    n = mb * b
    w = w_ref[...]                                          # (P, total) uint8
    p = w.shape[0]
    q, s, alpha = _wire_fields(w, n, mb, b, groups, folded, payload_dtype)
    f = s.reshape(p, mb, groups)
    if not folded:
        f = f / alpha[..., None]
    # ROW_TILE-shaped inverse rotations for bit-parity with
    # decompress_reduce_pallas (see ash_compress._row_tiles's gemv note)
    for r0, rows in _row_tiles(mb):
        qt = q[:, r0:r0 + rows].astype(jnp.float32)
        ft = f[:, r0:r0 + rows]
        if rows != ROW_TILE:
            pad = ((0, 0), (0, ROW_TILE - rows), (0, 0))
            qt, ft = jnp.pad(qt, pad), jnp.pad(ft, pad)
        fe = jnp.repeat(ft, b // groups, axis=-1).reshape(p, ROW_TILE, b)
        acc = jnp.sum(qt * fe, axis=0)                      # rotated domain
        if apply_rotation:
            acc = rotate(acc, h_ref[...])                   # ONE inverse rot
        o_ref[r0:r0 + rows, :] = acc[:rows].astype(out_dtype)


def decompress_reduce_wire_pallas(wire: jax.Array, n: int, cfg,
                                  interpret: bool = False):
    """Peer-stacked packed wire rows (P, total_bytes) -> summed (mb, B).

    The ReduceScatter local reduction fused with wire consumption: one
    kernel bitcasts every peer's payload/metadata out of the stacked wire
    buffer, accumulates in the rotated domain, and applies ONE inverse
    rotation (DESIGN.md §7.2).  Single grid step — the whole peer stack is
    one VMEM-resident wire tile (chunked ring transports keep per-chunk
    slots small by construction).  Not jit-wrapped."""
    fmt = cfg.format_spec
    peers, total = wire.shape
    b = cfg.block_size
    mb, groups, _, _, want = wire_geometry(cfg, n)
    if total != want:
        raise ValueError(f"wire row has {total} bytes, layout for n={n} "
                         f"declares {want}")
    h = ash_mod.hadamard_matrix(b, jnp.float32)
    kernel = functools.partial(
        _decompress_reduce_wire_kernel, mb=mb, b=b, groups=groups,
        folded=(cfg.metadata == "folded"),
        payload_dtype=fmt.dtype if fmt.is_float else jnp.int8,
        apply_rotation=cfg.transform in ("ash", "hadamard"),
        out_dtype=cfg.compute_dtype)
    return pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((peers, total), lambda i: (0, 0)),
            pl.BlockSpec((b, b), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((mb, b), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((mb, b), cfg.compute_dtype),
        interpret=interpret,
    )(wire, h)
