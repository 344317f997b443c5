"""Low-bit formats and Dual-Scale quantization — paper §3, §4.3.

Supports the paper's ablation grid:
  * FP8 E4M3 (the production format; Q_max = 448)
  * FP8 E5M2 (more range, 2-bit mantissa; Q_max = 57344)
  * INT8     (uniform grid — shown by the paper to be unsuitable for TP
              tensors; kept for the Fig. 5/6/14 reproductions and as the
              paper §6 "graceful degradation" path for non-FP8 hardware)

Dual-Scale quantization (Eq. 9-10): a per-group scale s = max|Z|/Q_max maps
the rotated block exactly into the representable range; ``quant_group_size``
lets s be computed at a finer granularity than the ASH block (the regime
where the alpha/s dual-scale pair is NOT mathematically collapsible).
"""
from __future__ import annotations

import dataclasses
from typing import Literal

import jax
import jax.numpy as jnp

from repro import compat

__all__ = ["FORMATS", "FormatSpec", "get_format", "quantize_ds",
           "dequantize_ds"]

FormatName = Literal["e4m3", "e5m2", "int8"]


@dataclasses.dataclass(frozen=True)
class FormatSpec:
    name: str
    dtype: object          # storage dtype (fp8 variants) or int8
    qmax: float            # largest representable magnitude
    is_float: bool

    @property
    def wire_dtype(self):
        """dtype actually placed on the wire (uint8 bitcast for fp8)."""
        return jnp.uint8 if self.is_float else jnp.int8


FORMATS: dict[str, FormatSpec] = {
    "int8": FormatSpec("int8", jnp.int8, 127.0, False),
    "e4m3": FormatSpec("e4m3", compat.FLOAT8_E4M3, 448.0, True),
    "e5m2": FormatSpec("e5m2", compat.FLOAT8_E5M2, 57344.0, True),
}


def get_format(name: str) -> FormatSpec:
    return FORMATS[name]


def _group(z: jax.Array, group_size: int) -> jax.Array:
    m, b = z.shape
    if group_size == b:
        return z[:, None, :]
    if b % group_size:
        raise ValueError(f"group_size {group_size} must divide block {b}")
    return z.reshape(m, b // group_size, group_size)


def quantize_ds(
    z: jax.Array,
    fmt: FormatSpec,
    *,
    group_size: int | None = None,
    eps: float = 1e-30,
) -> tuple[jax.Array, jax.Array]:
    """Dual-scale quantize rotated blocks ``z`` (M, B) -> (q, s).

    s has shape (M, B/group) — one scale per quantization group (default:
    one per ASH block, the paper's configuration).
    q keeps the (M, B) layout in the format's storage dtype.
    """
    m, b = z.shape
    gs = group_size or b
    zg = _group(z, gs)
    s = jnp.max(jnp.abs(zg), axis=-1) / fmt.qmax  # (M, B/gs)
    s = jnp.maximum(s, eps)
    scaled = zg / s[..., None]
    scaled = jnp.clip(scaled, -fmt.qmax, fmt.qmax)
    if fmt.is_float:
        q = scaled.astype(fmt.dtype)
    else:
        q = jnp.round(scaled).astype(jnp.int8)
    return q.reshape(m, b), s


def dequantize_ds(
    q: jax.Array,
    s: jax.Array,
    fmt: FormatSpec,
    *,
    compute_dtype=jnp.float32,
) -> jax.Array:
    """Inverse of quantize_ds: (M, B) payload + (M, B/gs) scales -> z_hat."""
    m, b = q.shape
    groups = s.shape[-1]
    gs = b // groups
    zg = q.astype(compute_dtype).reshape(m, groups, gs)
    return (zg * s[..., None].astype(compute_dtype)).reshape(m, b)
