"""Fused ASH-compress Pallas TPU kernel — paper §4.4.1, TPU-adapted.

One kernel performs, per (R, B) tile held in VMEM:
  1. RMS-energy reduction  sigma_k            (paper: warp shuffle #1)
  2. adaptive rescale      alpha_k = tau/sigma
  3. Hadamard rotation     Z = (alpha*G) @ (H/sqrt(B))   -> MXU matmul
  4. max-abs reduction     s_k = max|Z| / Q_max          (paper: warp shuffle #2)
  5. FP8 convert           q = cvt_fp8(Z / s)

i.e. exactly one HBM read of the tensor and one HBM write of the payload +
metadata — the GPU kernel's "single fused operator with both reductions
coalesced" property, with the rotation moved from a shared-memory butterfly
onto the systolic MXU (DESIGN.md §2).

Tiling: grid over row-tiles of R=128 blocks; each tile is (128, B) f32 in,
(128, B) fp8 + (128,) + (128, G) out. For B=256 the VMEM working set is
~0.4 MB — far under the ~16 MB/core budget, so the kernel is purely
bandwidth-bound, which is the point: compression must not steal MXU time
from the surrounding matmuls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import ash as ash_mod

ROW_TILE = 128


def rotate(x, h):
    """``x @ h`` contracted in f32 on the MXU.  XLA's TPU default rounds
    f32 dot operands to bf16; the kernels state their precision so their
    on-chip output matches the f32 reference (``kernels/ref.py``)."""
    return jnp.dot(x, h, precision=jax.lax.Precision.HIGHEST)


def _block_compress(g, h, *, tau, eps, scale_eps, qmax, groups, out_dtype,
                    is_float):
    """Shared per-block-row math of both compress kernels: (R, B) f32 ->
    (q (R,B) storage dtype, alpha (R,), s (R,G)).  Every op is row-wise
    independent, and both kernels invoke it at the same (ROW_TILE, B)
    tile shape (see ``_row_tiles``), so the block and fused-wire paths
    produce bit-identical rows — the wire fast path's parity contract."""
    b = g.shape[-1]
    # -- reduction 1: block RMS energy ------------------------------------
    sigma = jnp.sqrt(jnp.mean(g * g, axis=-1) + eps)        # (R,)
    alpha = tau / sigma                                     # (R,)
    # -- rotation on the MXU ----------------------------------------------
    z = rotate(alpha[:, None] * g, h)                       # (R, B)
    # -- reduction 2: per-group max magnitude ------------------------------
    # over static lane slices: Mosaic refuses the (R, B) -> (R, G, B/G)
    # reshape of a grouped reduction
    gs = b // groups
    amax = jnp.concatenate(
        [jnp.max(jnp.abs(z[:, k * gs:(k + 1) * gs]), axis=-1, keepdims=True)
         for k in range(groups)], axis=-1)                  # (R, G)
    s = jnp.maximum(amax / qmax, scale_eps)   # cfg.scale_eps, as in the ref
    # -- saturating convert -------------------------------------------------
    scaled = jnp.clip(z / jnp.repeat(s, gs, axis=-1), -qmax, qmax)
    if is_float:
        q = scaled.astype(out_dtype)
    else:
        q = jnp.round(scaled).astype(jnp.int8)
    return q, alpha, s


def _compress_kernel(x_ref, h_ref, q_ref, alpha_ref, s_ref, *, tau, eps,
                     scale_eps, qmax, groups, out_dtype, is_float):
    g = x_ref[...].astype(jnp.float32)                      # (R, B)
    q, alpha, s = _block_compress(
        g, h_ref[...], tau=tau, eps=eps, scale_eps=scale_eps, qmax=qmax,
        groups=groups, out_dtype=out_dtype, is_float=is_float)
    q_ref[...] = q
    alpha_ref[...] = alpha[:, None]
    s_ref[...] = s


def supported(cfg) -> bool:
    """The Pallas fast path implements the production TACO configuration."""
    return cfg.transform == "ash" and cfg.scale_granularity == "block"


def compress_blocks_pallas(blocks: jax.Array, cfg, interpret: bool = False):
    """(M, B) -> (q (M,B) storage dtype, alpha (M,), s (M,G)). M % 128 == 0
    is handled by padding here (padded rows are discarded by the caller).

    Deliberately NOT wrapped in its own ``jax.jit``: every production call
    site (``ops.compress_blocks`` under the collective/model jit) already
    traces inside an outer jit, where a nested jit only adds dispatch and
    trace-cache overhead on the hot path.
    """
    fmt = cfg.format_spec
    m, b = blocks.shape
    gs = cfg.quant_group_size or b
    groups = b // gs
    mp = ((m + ROW_TILE - 1) // ROW_TILE) * ROW_TILE
    if mp != m:
        blocks = jnp.pad(blocks, ((0, mp - m), (0, 0)))
    h = ash_mod.hadamard_matrix(b, jnp.float32)

    kernel = functools.partial(
        _compress_kernel, tau=cfg.tau, eps=cfg.eps, scale_eps=cfg.scale_eps,
        qmax=fmt.qmax, groups=groups, out_dtype=fmt.dtype,
        is_float=fmt.is_float)

    q, alpha, s = pl.pallas_call(
        kernel,
        grid=(mp // ROW_TILE,),
        in_specs=[
            pl.BlockSpec((ROW_TILE, b), lambda i: (i, 0)),
            pl.BlockSpec((b, b), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((ROW_TILE, b), lambda i: (i, 0)),
            pl.BlockSpec((ROW_TILE, 1), lambda i: (i, 0)),
            pl.BlockSpec((ROW_TILE, groups), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, b), fmt.dtype),
            jax.ShapeDtypeStruct((mp, 1), jnp.float32),
            jax.ShapeDtypeStruct((mp, groups), jnp.float32),
        ],
        interpret=interpret,
    )(blocks, h)
    alpha = alpha.reshape(mp)
    if mp != m:
        q, alpha, s = q[:m], alpha[:m], s[:m]
    return q, alpha, s


# --------------------------------------------------------------------------
# fused wire emission (paper §4.4 "highly fused compression operator"):
# compress AND serialize in one kernel — the payload, per-group scales,
# and alpha land at their static wire_layout(n) byte offsets of ONE packed
# uint8 output row, so the transport ships the kernel's output buffer
# as-is (single HBM write; no pack_wire concat copy).
# --------------------------------------------------------------------------

def wire_geometry(cfg, n: int):
    """Static byte geometry of one ``n``-element wire slot: ``(mb, groups,
    scale_nbytes, alpha_nbytes, total_bytes)``, derived from
    ``repro.core.taco.wire_components`` — the kernels serialize to the
    SAME layout contract the transport packs/unpacks, by construction."""
    import numpy as np

    from repro.core import taco as taco_mod

    comps = {name: (dtype, size)
             for name, dtype, size in taco_mod.wire_components(cfg, n)}
    mb = n // cfg.block_size
    scale_nbytes = comps["scale"][1] * np.dtype(comps["scale"][0]).itemsize
    groups = comps["scale"][1] // mb
    alpha_nbytes = 0
    if "alpha" in comps:
        alpha_nbytes = comps["alpha"][1] * \
            np.dtype(comps["alpha"][0]).itemsize
    return mb, groups, scale_nbytes, alpha_nbytes, n + scale_nbytes + \
        alpha_nbytes


def _row_tiles(mb):
    """Static (row0, rows) spans covering ``mb`` block rows in ROW_TILE
    batches.  The fused wire kernels iterate these so every matmul runs at
    the block kernels' exact (ROW_TILE, B) shape (partial tiles are
    zero-padded to ROW_TILE): XLA:CPU dispatches 1-row dots down a gemv
    path with a different accumulation schedule than gemm, so matching
    tile shapes — not just row-wise math — is what makes the fused and
    per-component paths bit-identical in interpret mode."""
    return [(r0, min(ROW_TILE, mb - r0)) for r0 in range(0, mb, ROW_TILE)]


def _pad_rows(a, rows, *, value=0.0):
    if a.shape[0] == rows:
        return a
    pad = [(0, rows - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, pad, constant_values=value)


def _compress_wire_kernel(x_ref, h_ref, w_ref, *, tau, eps, scale_eps, qmax,
                          groups, out_dtype, is_float, mb, b, folded):
    n = mb * b
    g = x_ref[...].reshape(mb, b).astype(jnp.float32)       # one slot's blocks
    s_off, a_off = n, n + mb * groups * 4
    for r0, rows in _row_tiles(mb):
        q, alpha, s = _block_compress(
            _pad_rows(g[r0:r0 + rows], ROW_TILE), h_ref[...], tau=tau,
            eps=eps, scale_eps=scale_eps, qmax=qmax, groups=groups,
            out_dtype=out_dtype, is_float=is_float)
        q, alpha, s = q[:rows], alpha[:rows], s[:rows]
        # serialize straight into the packed wire row: per-tile stores at
        # the static byte offsets of ONE output buffer (no concatenate —
        # the interpret-mode HLO between encode and the collective is
        # concat-free, and on TPU each store is a VMEM->HBM tile write)
        w_ref[0, r0 * b:r0 * b + rows * b] = \
            jax.lax.bitcast_convert_type(q, jnp.uint8).reshape(rows * b)
        meta = (s / alpha[:, None]) if folded else s        # (rows, G) f32
        w_ref[0, s_off + r0 * groups * 4:
              s_off + (r0 + rows) * groups * 4] = \
            jax.lax.bitcast_convert_type(meta, jnp.uint8).reshape(
                rows * groups * 4)
        if not folded:
            w_ref[0, a_off + r0 * 4:a_off + (r0 + rows) * 4] = \
                jax.lax.bitcast_convert_type(alpha, jnp.uint8).reshape(
                    rows * 4)


def compress_wire_pallas(x: jax.Array, cfg, interpret: bool = False):
    """(slots, n) -> (slots, total_bytes) packed uint8 wire buffer.

    One grid step per slot: all ``n // block_size`` blocks of the slot are
    compressed and serialized to the slot's contiguous wire row in a
    single pass (VMEM working set: the slot + the Hadamard matrix).
    Bit-identical to ``pack_wire(TacoCodec.encode(x), wire_layout(n))`` on
    the same impl — the per-row math is shared with ``_compress_kernel``.
    Not jit-wrapped: call sites always sit under an outer jit."""
    fmt = cfg.format_spec
    slots, n = x.shape
    b = cfg.block_size
    mb, groups, _, _, total = wire_geometry(cfg, n)
    h = ash_mod.hadamard_matrix(b, jnp.float32)
    kernel = functools.partial(
        _compress_wire_kernel, tau=cfg.tau, eps=cfg.eps,
        scale_eps=cfg.scale_eps, qmax=fmt.qmax, groups=groups,
        out_dtype=fmt.dtype, is_float=fmt.is_float, mb=mb, b=b,
        folded=(cfg.metadata == "folded"))
    return pl.pallas_call(
        kernel,
        grid=(slots,),
        in_specs=[
            pl.BlockSpec((1, n), lambda i: (i, 0)),
            pl.BlockSpec((b, b), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, total), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((slots, total), jnp.uint8),
        interpret=interpret,
    )(x, h)
