"""Jit'd dispatch wrappers over the TACO operators.

Selects between the Pallas TPU kernels (fast path for the production TACO
configuration), Pallas interpret mode (CPU validation of the exact kernel
body), and the pure-jnp reference (oracle; also the CPU/dry-run path and
the only path for ablation configurations the kernel doesn't implement).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ash_compress, ash_decompress, ref


def _impl_for(cfg) -> str:
    """The impl that runs ``cfg``.  ``auto`` takes the jnp reference for
    the ablation configs the kernels do not implement; an explicit kernel
    impl on such a config is an error, never a silent swap."""
    impl = cfg.resolved_impl()
    if impl in ("pallas", "pallas_interpret") and not ash_compress.supported(cfg):
        if cfg.impl == "auto":
            return "jnp"
        raise ValueError(
            f"impl={cfg.impl!r} has no kernel for transform="
            f"{cfg.transform!r}, scale_granularity={cfg.scale_granularity!r}"
            " (the kernels implement transform='ash' with block scales); "
            "use impl='auto' or 'jnp'")
    return impl


def compress_blocks(blocks: jax.Array, cfg):
    """(M, B) -> (q storage dtype, alpha (M,), s (M,G))."""
    impl = _impl_for(cfg)
    if impl == "jnp":
        return ref.compress_blocks_ref(blocks, cfg)
    return ash_compress.compress_blocks_pallas(
        blocks, cfg, interpret=(impl == "pallas_interpret"))


def decompress_blocks(q: jax.Array, s: jax.Array, alpha, cfg):
    """(q, s, alpha|None) -> blocks (M, B) in cfg.compute_dtype."""
    impl = _impl_for(cfg)
    if impl == "jnp":
        out = ref.decompress_blocks_ref(q, s, alpha, cfg)
        return out.astype(cfg.compute_dtype)
    return ash_decompress.decompress_blocks_pallas(
        q, s, alpha, cfg, interpret=(impl == "pallas_interpret"))


def decompress_reduce(q: jax.Array, s: jax.Array, alpha, cfg):
    """Stacked-peer fused dequant+reduce: q (P,M,B) -> summed blocks (M,B).

    jnp path also uses the rotated-domain single-rotation identity so CPU
    dry-runs see the same FLOP structure as the TPU kernel.
    """
    impl = _impl_for(cfg)
    if impl == "jnp":
        from repro.core import ash as ash_mod
        peers, m, b = q.shape
        groups = s.shape[-1]
        f = s if alpha is None else s / alpha[..., None]       # (P, M, G)
        # grouped einsum broadcasts the per-group scale over each group's
        # elements inside the contraction — no materialized (P, M, B)
        # f32 scale tensor on the dry-run/CPU path
        zsum = jnp.einsum(
            "pmgk,pmg->mgk",
            q.reshape(peers, m, groups, b // groups).astype(cfg.compute_dtype),
            f.astype(cfg.compute_dtype),
        ).reshape(m, b)
        if cfg.transform in ("ash", "hadamard"):
            zsum = zsum @ ash_mod.hadamard_matrix(b, cfg.compute_dtype)
        return zsum
    return ash_decompress.decompress_reduce_pallas(
        q, s, alpha, cfg, interpret=(impl == "pallas_interpret"))


# --------------------------------------------------------------------------
# fused wire-native fast paths (TacoCodec.encode_wire/decode_wire/
# decode_sum_wire dispatch here; the jnp impl has no fused kernel and the
# codec composes pack_wire/unpack_wire with encode/decode instead)
# --------------------------------------------------------------------------

# The fused wire kernels run in interpret mode only.  Compiled for TPU
# (v5e), Mosaic refuses them: the one-slot (1, n) blocks break the
# (8, 128) block rule, and serializing f32 metadata into the uint8 row
# needs an in-kernel bitcast that changes bit width ("Changing bitwidths
# not supported").  So on the device every hop takes the block kernels
# + pack_wire, at any slot size; tests/test_wire_fused.py pins the rule.
def wire_kernel_impl(cfg):
    """``"pallas_interpret"`` when the fused wire kernels run ``cfg``,
    else None (jnp, and the compiled TPU impl — see above)."""
    impl = _impl_for(cfg)
    return impl if impl == "pallas_interpret" else None


def compress_wire(x: jax.Array, cfg):
    """(slots, n) -> packed (slots, total_bytes) uint8 wire buffer."""
    return ash_compress.compress_wire_pallas(x, cfg, interpret=True)


def decompress_wire(wire: jax.Array, n: int, cfg):
    """Packed (slots, total_bytes) uint8 -> (slots, n) compute dtype."""
    return ash_decompress.decompress_wire_pallas(wire, n, cfg, interpret=True)


def decompress_reduce_wire(wire: jax.Array, n: int, cfg):
    """Peer-stacked (P, total_bytes) wire rows -> fused summed (mb, B)."""
    return ash_decompress.decompress_reduce_wire_pallas(
        wire, n, cfg, interpret=True)
