"""Wire codecs: a uniform interface over the compression schemes used on
each communication path of the 3D-parallel stack (paper §4.4.2 + §5.5):

  * ``TacoCodec``     — TP intermediate tensors (FP8 ASH+DS; the paper).
  * ``Sdp4BitCodec``  — DP gradient reduce-scatter (int4 + rotation).
  * ``TahQuantCodec`` — PP stage boundaries (group int8).
  * ``Int8Codec``     — weight all-gather compression (beyond-paper knob).
  * ``IdentityCodec`` — no compression (baseline); collectives special-case
    it to native lax collectives so the baseline HLO is untouched.

All codecs operate on 2-D ``(slots, n)`` arrays where ``slots`` is a chunk/
peer dimension and ``n`` (static) is a multiple of ``granule``. ``encode``
returns a tuple of arrays that the collective layer transports; ``decode``
inverts; ``decode_sum`` reduces a stacked peer axis during ReduceScatter
(fused, rotated-domain where applicable).

Every compressing codec also publishes a :class:`WireLayout` via
``wire_layout(n)`` — the byte offsets/dtypes of its encoded components per
slot — which lets the collective layer move all components as ONE
contiguous uint8 wire buffer per hop (one lax collective instead of 2–3),
and a ``chunks`` knob selecting the chunked ring-overlap transport
(``chunks=N`` double-buffered wire slices; see
``repro.core.collectives``).  ``IdentityCodec.wire_layout`` returns None:
the baseline transports the raw tensor and has nothing to pack.

Slots may be *bounded-but-ragged*: a layout with ``variable=True``
(lossless/hybrid stacks, ``repro.core.lossless``) still moves a
static-width buffer of ``total_bytes`` — the worst-case bound — but only
a data-dependent prefix carries information, recorded in a uint32 length
header at static byte offset 0 (:func:`achieved_wire_bytes` reads it
back).  The fixed-width layouts of the lossy codecs below are the
degenerate case where achieved == slot bytes.

Chunked codecs additionally carry a ``schedule`` knob (spec token
``schedule=pipelined|serial``, default ``pipelined``) choosing how the
ring transport orders the per-chunk stages: ``pipelined`` emits the
software-pipelined (encode[c], transfer[c-1], decode[c-2]) stage schedule
fenced with optimization barriers (``repro.core.overlap``), ``serial``
keeps the hoisted all-encodes-first ordering for parity testing.  Both
are bit-identical; ``schedule`` is ignored when ``chunks == 1`` (the
monolithic transport has a single stage of each kind).

Every compressing codec also carries the error-escalation policy knobs
(spec tokens ``escalate=<fallback>@<threshold>`` / ``hold=<N>``): when
set, the transport emits a sampled relative-quantization-error probe and
a ``repro.core.policy.ErrorEscalationController`` swaps the path to the
registered higher-precision fallback codec while the error EMA sits
above the threshold (de-escalating after a ``hold``-step hysteresis
window).  ``escalate=None`` (the default) traces ZERO probe ops — the
lowered HLO is byte-identical to a codec without the fields.

Wire-native fast paths: the transport calls ``encode_wire(x)`` /
``decode_wire(wire, n, dtype)`` / ``decode_sum_wire(wire, n, dtype)``
rather than composing ``encode`` with :func:`pack_wire` itself.  The
generic :class:`WireFastPath` implementations ARE that composition — they
define the wire format — while codecs with fused kernels (TACO) override
them to emit/consume the packed buffer straight from the Pallas kernel
(one HBM write, no concat-and-slice copies; paper §4.4 "highly fused
compression operator").  Overrides must stay bit-identical to the generic
path — property-tested in tests/test_wire_fused.py.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dp_compress, pp_compress
from repro.core.overlap import PIPELINED
from repro.core.telemetry import SCOPE_WIRE
from repro.core.taco import TacoConfig
from repro.kernels import ops as kops

__all__ = [
    "IdentityCodec", "TacoCodec", "Sdp4BitCodec", "TahQuantCodec",
    "Int8Codec", "wire_bytes_per_element", "WireComponent", "WireLayout",
    "make_wire_layout", "pack_wire", "unpack_wire", "WireFastPath",
    "achieved_wire_bytes", "DEFAULT_HOLD",
]


# --------------------------------------------------------------------------
# wire layout: the static byte format of one encoded slot
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class WireComponent:
    """One encoded component inside the packed wire buffer: ``size``
    elements of ``dtype`` (a numpy dtype name) starting at byte
    ``offset`` of the slot's contiguous uint8 wire row."""

    name: str
    dtype: str
    size: int
    offset: int

    @property
    def itemsize(self) -> int:
        return np.dtype(self.dtype).itemsize

    @property
    def nbytes(self) -> int:
        return self.size * self.itemsize


@dataclasses.dataclass(frozen=True)
class WireLayout:
    """Per-slot wire format: components in ``encode`` output order,
    densely packed (offset_i+1 == offset_i + nbytes_i).

    ``total_bytes`` is always the STATIC slot width — the size of the
    uint8 buffer the collective layer actually moves.  A layout with
    ``variable=True`` declares a *bounded-but-ragged* slot: the buffer is
    still ``total_bytes`` wide (lax collectives need static shapes and
    the bound is what a real transport must reserve), but only a
    data-dependent prefix of it carries information, and the slot's FIRST
    component must be a one-element ``uint32`` length header at byte
    offset 0 recording the achieved bytes.  :func:`achieved_wire_bytes`
    reads it back; padding bytes past the achieved length are zero."""

    components: tuple
    variable: bool = False

    @property
    def total_bytes(self) -> int:
        if not self.components:
            return 0
        last = self.components[-1]
        return last.offset + last.nbytes

    def __post_init__(self):
        if self.variable:
            c0 = self.components[0] if self.components else None
            if c0 is None or c0.offset != 0 or c0.dtype != "uint32" \
                    or c0.size != 1:
                raise ValueError(
                    "variable WireLayout requires a 1-element uint32 "
                    "length header as its first component (offset 0)")


def make_wire_layout(*comps, variable: bool = False) -> WireLayout:
    """Build a dense :class:`WireLayout` from ``(name, dtype, size)``
    triples, computing byte offsets.  ``variable=True`` marks a
    bounded-but-ragged slot (first component must then be the uint32
    length header — see :class:`WireLayout`)."""
    out, off = [], 0
    for name, dtype, size in comps:
        c = WireComponent(name, np.dtype(dtype).name, int(size), off)
        out.append(c)
        off += c.nbytes
    return WireLayout(tuple(out), variable=variable)


def achieved_wire_bytes(wire, layout):
    """Per-slot ACHIEVED (data-dependent) bytes of a packed wire buffer.

    For a ``variable`` layout this reads the uint32 length header at byte
    offset 0 of every slot; for a static layout every slot achieves its
    full ``total_bytes`` (the two notions coincide — the degenerate
    fixed-length case).  ``wire`` is ``(..., total_bytes)`` uint8 with any
    number of leading slot/peer axes; returns a ``(...,)`` uint32 array."""
    if not layout.variable:
        return jnp.full(wire.shape[:-1], layout.total_bytes, jnp.uint32)
    hdr = _from_bytes(wire[..., 0:4], "uint32", 1)
    return hdr[..., 0]


# --------------------------------------------------------------------------
# wire pack/unpack: bitcast plumbing between a codec's component tuple and
# the single contiguous uint8 wire buffer (the copy path; fused kernels
# write the same byte layout directly)
# --------------------------------------------------------------------------

def _to_bytes(a):
    """Bitcast any wire component to a flat-per-slot uint8 view."""
    if a.dtype == jnp.uint8:
        return a
    if a.dtype.itemsize == 1:
        return jax.lax.bitcast_convert_type(a, jnp.uint8)
    u8 = jax.lax.bitcast_convert_type(a, jnp.uint8)   # (..., k, itemsize)
    return u8.reshape(*a.shape[:-1], a.shape[-1] * a.dtype.itemsize)


def _from_bytes(seg, dtype, size):
    dt = jnp.dtype(dtype)
    if dt.itemsize == 1:
        return seg if dt == jnp.uint8 \
            else jax.lax.bitcast_convert_type(seg, dt)
    seg = seg.reshape(*seg.shape[:-1], size, dt.itemsize)
    return jax.lax.bitcast_convert_type(seg, dt)


@jax.named_scope(SCOPE_WIRE)
def pack_wire(enc, layout):
    """Encoded component tuple -> ONE contiguous uint8 buffer per slot,
    laid out per ``layout`` (bitcast + trailing-axis concatenation).

    The static width checks catch an encode/wire_layout disagreement at
    trace time — without them a mismatched codec would ship bit-garbage
    through unpack_wire's static slices with no exception anywhere."""
    if len(enc) != len(layout.components):
        raise ValueError(f"encode produced {len(enc)} components, layout "
                         f"declares {len(layout.components)}")
    parts = []
    for a, comp in zip(enc, layout.components):
        b = _to_bytes(a)
        if b.shape[-1] != comp.nbytes:
            raise ValueError(
                f"component {comp.name!r}: encode emitted {b.shape[-1]} "
                f"bytes/slot, layout declares {comp.nbytes}")
        parts.append(b)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)


@jax.named_scope(SCOPE_WIRE)
def unpack_wire(wire, layout):
    """Inverse of :func:`pack_wire`: slice the uint8 buffer at the static
    byte offsets and bitcast each component back.  Works with any number
    of leading (peer/slot) axes."""
    return tuple(
        _from_bytes(wire[..., c.offset:c.offset + c.nbytes], c.dtype, c.size)
        for c in layout.components)


#: Default de-escalation hysteresis window (steps) for ``escalate=``
#: codecs — shared by the dataclass fields and the spec normalizer.
DEFAULT_HOLD = 20


def _check_escalation(codec) -> None:
    """Validate the ``escalate``/``hold`` fields shared by every lossy
    codec (the registry additionally checks the fallback NAME against its
    fallback table — codecs cannot import the registry)."""
    esc = getattr(codec, "escalate", None)
    hold = getattr(codec, "hold", DEFAULT_HOLD)
    if not isinstance(hold, int) or hold < 1:
        raise ValueError(f"escalation hold must be an int >= 1, got {hold!r}")
    if esc is None:
        return
    if (not isinstance(esc, tuple) or len(esc) != 2
            or not isinstance(esc[0], str) or not esc[0]):
        raise ValueError("escalate must be a (fallback_name, threshold) "
                         f"tuple, got {esc!r}")
    thr = float(esc[1])
    if not thr > 0.0:
        raise ValueError(f"escalation threshold must be > 0, got {thr}")


class WireFastPath:
    """Generic wire-native paths: pack/unpack composed with encode/decode.

    These ARE the definition of the wire byte format.  Codecs with fused
    kernels override them (emitting/consuming the packed buffer directly
    in the kernel) and must stay bit-identical to these compositions —
    the contract the transport's HLO-count and parity tests rely on."""

    def __post_init__(self):
        _check_escalation(self)

    def encode_wire(self, x):
        """(slots, n) -> (slots, total_bytes) uint8 wire buffer."""
        return pack_wire(self.encode(x), self.wire_layout(x.shape[-1]))

    def decode_wire(self, wire, n, dtype):
        """(..., total_bytes) uint8 -> (..., n) decoded in ``dtype``."""
        return self.decode(unpack_wire(wire, self.wire_layout(n)), n, dtype)

    def decode_sum_wire(self, wire, n, dtype):
        """(P, ..., total_bytes) uint8 -> peer-summed decode (fused)."""
        return self.decode_sum(unpack_wire(wire, self.wire_layout(n)),
                               n, dtype)


@dataclasses.dataclass(frozen=True)
class IdentityCodec:
    granule: int = 1
    chunks: int = 1   # fixed; the baseline has no wire layout to slice

    def wire_layout(self, n):
        return None   # transports the raw tensor — nothing to pack

    def encode_wire(self, x):
        raise TypeError("IdentityCodec transports raw tensors and has no "
                        "wire form (wire_layout() is None)")

    def decode_wire(self, wire, n, dtype):
        raise TypeError("IdentityCodec has no wire form")

    def decode_sum_wire(self, wire, n, dtype):
        raise TypeError("IdentityCodec has no wire form")

    def encode(self, x):
        return (x,)

    def decode(self, enc, n, dtype):
        return enc[0].astype(dtype)

    def decode_sum(self, enc, n, dtype):
        # Accumulate the peer axis in f32 (not the bf16 wire dtype): the
        # uncompressed reduce-scatter baseline must not lose low-order
        # gradient mass to bf16 sequential summation.
        x = enc[0]
        if jnp.issubdtype(x.dtype, jnp.floating) and \
                jnp.finfo(x.dtype).bits < 32:
            x = x.astype(jnp.float32)
        return jnp.sum(x, axis=0).astype(dtype)

    def bytes_per_element(self, in_dtype=jnp.bfloat16) -> float:
        return np.dtype(in_dtype).itemsize


@dataclasses.dataclass(frozen=True)
class TacoCodec(WireFastPath):
    """The paper's compressor. Payload uint8 (bitcast fp8/int8) + scales.

    On the interpret impl the wire-native methods dispatch to the fused
    kernels (``kernels.ash_compress.compress_wire_pallas`` and friends)
    that read/write the packed uint8 buffer at its static
    ``wire_layout(n)`` byte offsets directly — no pack/unpack copies.
    The compiled TPU impl runs the block kernels + ``pack_wire``."""

    cfg: TacoConfig = TacoConfig()
    chunks: int = 1
    schedule: str = PIPELINED
    escalate: tuple | None = None   # (fallback_name, error threshold)
    hold: int = DEFAULT_HOLD

    @property
    def granule(self) -> int:
        return self.cfg.block_size

    def wire_layout(self, n):
        from repro.core import taco as taco_mod
        return make_wire_layout(*taco_mod.wire_components(self.cfg, n))

    def _split(self, x):
        slots, n = x.shape
        b = self.cfg.block_size
        return x.reshape(slots * (n // b), b), n // b

    def encode(self, x):
        from repro.core import taco as taco_mod
        slots, n = x.shape
        blocks, mb = self._split(x)
        q, alpha, s = kops.compress_blocks(blocks, self.cfg)
        payload = taco_mod._storage_to_wire(q, self.cfg.format_spec)
        payload = payload.reshape(slots, n)
        groups = s.shape[-1]
        if self.cfg.metadata == "folded":
            return payload, (s / alpha[:, None]).reshape(slots, mb * groups)
        return payload, s.reshape(slots, mb * groups), alpha.reshape(slots, mb)

    def _meta(self, enc, slots_shape):
        b = self.cfg.block_size
        groups = b // (self.cfg.quant_group_size or b)
        if self.cfg.metadata == "folded":
            payload, s = enc
            return payload, s, None, groups
        payload, s, alpha = enc
        return payload, s, alpha, groups

    def decode(self, enc, n, dtype):
        from repro.core import taco as taco_mod
        payload, s, alpha, groups = self._meta(enc, None)
        slots = payload.shape[0]
        b = self.cfg.block_size
        m = slots * (n // b)
        q = taco_mod._wire_to_storage(payload.reshape(m, b), self.cfg.format_spec)
        s = s.reshape(m, groups)
        alpha = None if alpha is None else alpha.reshape(m)
        out = kops.decompress_blocks(q, s, alpha, self.cfg)
        return out.reshape(slots, n).astype(dtype)

    def decode_sum(self, enc, n, dtype):
        from repro.core import taco as taco_mod
        payload, s, alpha, groups = self._meta(enc, None)
        p = payload.shape[0]
        b = self.cfg.block_size
        m = (payload.size // p) // b
        q = taco_mod._wire_to_storage(payload.reshape(p, m, b), self.cfg.format_spec)
        s = s.reshape(p, m, groups)
        alpha = None if alpha is None else alpha.reshape(p, m)
        out = kops.decompress_reduce(q, s, alpha, self.cfg)
        return out.reshape(-1)[:n].astype(dtype) if out.ndim > 1 else out.astype(dtype)

    def bytes_per_element(self, in_dtype=jnp.bfloat16) -> float:
        b = self.cfg.block_size
        groups = b // (self.cfg.quant_group_size or b)
        scalars = groups + (0 if self.cfg.metadata == "folded" else 1)
        return 1.0 + 4.0 * scalars / b

    # ---- fused wire-native fast paths (interpret mode; see
    # kernels.ops.wire_kernel_impl for why the TPU impl packs instead) ----
    def encode_wire(self, x):
        if kops.wire_kernel_impl(self.cfg) is not None:
            return kops.compress_wire(x, self.cfg)
        return super().encode_wire(x)

    def decode_wire(self, wire, n, dtype):
        if kops.wire_kernel_impl(self.cfg) is not None:
            lead = wire.shape[:-1]
            out = kops.decompress_wire(
                wire.reshape(-1, wire.shape[-1]), n, self.cfg)
            return out.reshape(*lead, n).astype(dtype)
        return super().decode_wire(wire, n, dtype)

    def decode_sum_wire(self, wire, n, dtype):
        # the fused reduce kernel consumes a (P, total_bytes) peer stack;
        # other stackings take the generic unpack path
        if wire.ndim == 2 and kops.wire_kernel_impl(self.cfg) is not None:
            out = kops.decompress_reduce_wire(wire, n, self.cfg)
            return out.reshape(-1)[:n].astype(dtype)
        return super().decode_sum_wire(wire, n, dtype)


@dataclasses.dataclass(frozen=True)
class Sdp4BitCodec(WireFastPath):
    block: int = 128
    rotate: bool = True
    chunks: int = 1
    schedule: str = PIPELINED
    escalate: tuple | None = None   # (fallback_name, error threshold)
    hold: int = DEFAULT_HOLD

    @property
    def granule(self) -> int:
        return self.block

    def wire_layout(self, n):
        return make_wire_layout(("payload", "uint8", n // 2),
                                ("scale", "float32", n // self.block))

    def encode(self, x):
        return dp_compress.compress_int4(x, self.block, self.rotate)

    def decode(self, enc, n, dtype):
        packed, s = enc
        return dp_compress.decompress_int4(packed, s, n, self.block, self.rotate, dtype)

    def decode_sum(self, enc, n, dtype):
        packed, s = enc
        return dp_compress.decompress_sum_int4(
            packed, s, n, self.block, self.rotate, dtype).reshape(-1)[:n]

    def bytes_per_element(self, in_dtype=jnp.bfloat16) -> float:
        return 0.5 + 4.0 / self.block


@dataclasses.dataclass(frozen=True)
class TahQuantCodec(WireFastPath):
    group: int = 64
    chunks: int = 1
    schedule: str = PIPELINED
    escalate: tuple | None = None   # (fallback_name, error threshold)
    hold: int = DEFAULT_HOLD

    @property
    def granule(self) -> int:
        return self.group

    def wire_layout(self, n):
        return make_wire_layout(("payload", "int8", n),
                                ("scale", "float32", n // self.group))

    def encode(self, x):
        return pp_compress.compress_int8_group(x, self.group)

    def decode(self, enc, n, dtype):
        q, s = enc
        return pp_compress.decompress_int8_group(q, s, n, self.group, dtype)

    def decode_sum(self, enc, n, dtype):
        q, s = enc
        return pp_compress.decompress_sum_int8_group(
            q, s, n, self.group, dtype).reshape(-1)[:n]

    def bytes_per_element(self, in_dtype=jnp.bfloat16) -> float:
        return 1.0 + 4.0 / self.group


@dataclasses.dataclass(frozen=True)
class Int8Codec(WireFastPath):
    """Per-group int8 for weight all-gather (beyond-paper, DESIGN.md §7.3)."""

    group: int = 128
    chunks: int = 1
    schedule: str = PIPELINED
    escalate: tuple | None = None   # (fallback_name, error threshold)
    hold: int = DEFAULT_HOLD

    @property
    def granule(self) -> int:
        return self.group

    def wire_layout(self, n):
        return make_wire_layout(("payload", "int8", n),
                                ("scale", "float32", n // self.group))

    def encode(self, x):
        return pp_compress.compress_int8_group(x, self.group)

    def decode(self, enc, n, dtype):
        q, s = enc
        return pp_compress.decompress_int8_group(q, s, n, self.group, dtype)

    def decode_sum(self, enc, n, dtype):
        q, s = enc
        return pp_compress.decompress_sum_int8_group(
            q, s, n, self.group, dtype).reshape(-1)[:n]

    def bytes_per_element(self, in_dtype=jnp.bfloat16) -> float:
        return 1.0 + 4.0 / self.group


def wire_bytes_per_element(codec, in_dtype=jnp.bfloat16) -> float:
    return codec.bytes_per_element(in_dtype)
