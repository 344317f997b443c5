"""Butterfly-FWHT Pallas kernel — the GPU-style O(B log B) algorithm, kept
as a measurable counterpoint to the production MXU-matmul form
(DESIGN.md §2 hardware adaptation).

On an H100 the shared-memory butterfly is the right call (the paper's
choice); on TPU the log2(B) sequential stages serialize on the VPU while
the 256x256 +-1 matmul streams through the systolic MXU. This kernel
exists so the claim is *testable*: identical numerics (allclose vs both
the matmul kernel and the jnp oracle), different op structure — the
benchmark table reports flops per element of each form
(2*B matmul vs 2*log2(B) butterfly).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_TILE = 128


def _fwht_body(x):
    """In-register butterfly over the last axis (power of 2).  Stage ``h``
    pairs lane ``i`` with lane ``i ^ h``; the partner is fetched with a
    lane rotation (Mosaic refuses the reshape-based pairing)."""
    n = x.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    h = 1
    while h < n:
        upper = (lane & h) != 0
        partner = jnp.where(upper, pltpu.roll(x, h, x.ndim - 1),
                            pltpu.roll(x, n - h, x.ndim - 1))
        x = jnp.where(upper, partner - x, x + partner)
        h *= 2
    return x


def _compress_kernel(x_ref, q_ref, alpha_ref, s_ref, *, tau, eps, qmax,
                     out_dtype, is_float, inv_sqrt_b):
    g = x_ref[...].astype(jnp.float32)
    sigma = jnp.sqrt(jnp.mean(g * g, axis=-1) + eps)
    alpha = tau / sigma
    z = _fwht_body(alpha[:, None] * g) * inv_sqrt_b       # VPU butterfly
    s = jnp.maximum(jnp.max(jnp.abs(z), axis=-1) / qmax, 1e-30)
    scaled = jnp.clip(z / s[:, None], -qmax, qmax)
    q_ref[...] = scaled.astype(out_dtype) if is_float else \
        jnp.round(scaled).astype(jnp.int8)
    alpha_ref[...] = alpha[:, None]
    s_ref[...] = s[:, None]


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def compress_blocks_butterfly(blocks: jax.Array, cfg, interpret: bool = False):
    """Same contract as ash_compress.compress_blocks_pallas (block-level
    scales only)."""
    fmt = cfg.format_spec
    m, b = blocks.shape
    mp = ((m + ROW_TILE - 1) // ROW_TILE) * ROW_TILE
    if mp != m:
        blocks = jnp.pad(blocks, ((0, mp - m), (0, 0)))
    kernel = functools.partial(
        _compress_kernel, tau=cfg.tau, eps=cfg.eps, qmax=fmt.qmax,
        out_dtype=fmt.dtype, is_float=fmt.is_float,
        inv_sqrt_b=1.0 / float(b) ** 0.5)
    q, alpha, s = pl.pallas_call(
        kernel,
        grid=(mp // ROW_TILE,),
        in_specs=[pl.BlockSpec((ROW_TILE, b), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((ROW_TILE, b), lambda i: (i, 0)),
            pl.BlockSpec((ROW_TILE, 1), lambda i: (i, 0)),
            pl.BlockSpec((ROW_TILE, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, b), fmt.dtype),
            jax.ShapeDtypeStruct((mp, 1), jnp.float32),
            jax.ShapeDtypeStruct((mp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(blocks)
    alpha = alpha.reshape(mp)
    if mp != m:
        q, alpha, s = q[:m], alpha[:m], s[:m]
    return q, alpha, s


def flops_per_element(b: int) -> dict:
    """Structural cost of the two rotation forms (per tensor element)."""
    import math
    return {"mxu_matmul": 2 * b, "vpu_butterfly": 2 * math.log2(b)}
