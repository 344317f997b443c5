"""Compressed collectives — the paper's §4.4.2 communication layer on TPU.

All functions run INSIDE ``shard_map`` and operate on per-device local
arrays. Compression semantics follow COCCL's two-shot decomposition:

  ReduceScatter = one compressed AlltoAll + ONE fused local reduction
  AllGather     = one compressed AllGather + fused decompress
  AllReduce     = ReduceScatter ∘ AllGather  (two compressions per round)

Every collective takes a forward codec and a backward codec and installs a
``custom_vjp`` so the backward-pass communication (activation gradients /
parameter gradients) is compressed too — quantization is applied to the
cotangent straight-through, exactly as in the paper (no differentiation
through the quantizer).

All six public collectives are instances of ONE generic wrapper,
``_compressed_collective(impl, bwd)``: ``impl`` computes the forward
communication with the forward codec, ``bwd`` maps the cotangent through
the conjugate collective with the codec pair swapped. The shared
pad → encode → pack → move-one-wire-buffer → unpack → decode/decode_sum
→ crop plumbing lives in ``_transport``.

Wire packing (ZipCCL-style fused buffer): every compressing codec
publishes a static ``wire_layout(n)`` (byte offsets/dtypes of its encoded
components), and ``_transport`` moves all components as ONE contiguous
uint8 buffer per hop — each compressed all-gather / reduce-scatter /
ppermute / all-to-all issues exactly ONE lax collective instead of one
per component (2–3 before).  The buffer is produced/consumed through the
codec's wire-native fast paths (``encode_wire``/``decode_wire``/
``decode_sum_wire``): the generic codecs compose ``pack_wire``/
``unpack_wire`` (bitcast + concat, defined in ``repro.core.codecs`` and
re-exported here), while TACO's interpret impl emits and reads the
packed bytes straight from the fused kernels — no concat-and-slice
copies between compression and the collective (on TPU the fused wire
kernels do not compile, so TACO packs there too; see
``repro.kernels.ops.wire_kernel_impl``).  ``multibuffer_wire()`` restores
the per-component transport for parity tests and benchmarks.

Bounded-but-ragged slots: hybrid stacks (``taco+zle`` — see
``repro.core.lossless``) publish VARIABLE wire layouts, where the slot
width is a static worst-case bound and a uint32 length header records
the achieved (data-dependent) bytes.  The transport stays one collective
per hop, but the bound it moves is RENEGOTIABLE: a codec with
``slot="auto"`` carries a controller-set ``moved_frac`` (per-chunk
fractions of the slot bound), each hop truncates its wire buffer to the
negotiated width before the ONE lax collective and zero-repads after —
bit-exact whenever every slot's achieved bytes fit the truncation,
because a variable layout guarantees all bytes past the achieved width
are zero.  Hops on auto codecs also probe their achieved bytes out of
jit via ``jax.debug.callback``; the host-side :class:`SlotController`
drains the probes between steps, tracks a decaying high-watermark per
(codec, chunk), renegotiates ``moved_frac`` outside jit (like the
trainer's warmup resolution — a handful of quantized fractions, so jit
caches stay bounded), and on a per-hop OVERFLOW (achieved > negotiated)
flags a one-step static-slot resync so the path stays lossless — never
deadlocked, the worst case is one replayed step at the static bound.
The byte telemetry splits three ways: ``wire_slot_bytes`` is the static
bound, ``moved_slot_bytes`` the negotiated width the fabric carries,
``achieved_slot_bytes`` (and the ``sample=`` arg of the per-collective
byte counters) the data-dependent payload itself.

Chunked ring overlap (Flash-Communication-style): codecs with
``chunks=N > 1`` route their all-gather / reduce-scatter through ring
variants built from ``ppermute`` steps over N wire slices.  Chunk
streams carry no data dependencies on each other, so the encode of chunk
i+1 and the fused decode/decode_sum of chunk i−1 are free to overlap the
transfer of chunk i; the stage emission order is owned by
``repro.core.overlap`` — ``schedule=pipelined`` (the default) emits the
barrier-fenced software-pipelined (encode[c], transfer[c-1], decode[c-2])
tick schedule so XLA cannot hoist the encodes and re-serialize the
streams, ``schedule=serial`` keeps the hoisted all-encodes-first order
for parity testing.  Results are bit-identical across both schedules and
the monolithic path (contributions are compressed once and peer sums
happen at the destination in peer-index order).

Megatron conjugate pairs provided for both TP modes:
  SP mode        : ``all_gather_c``(seq) fwd / ``psum_scatter_c``(seq) bwd
  AllReduce mode : ``allreduce_g`` (fwd AR, bwd id) / ``copy_f`` (fwd id, bwd AR)

Tuple axis names (e.g. fsdp = ("pod","data")) are handled hierarchically,
innermost axis first for gathers and outermost first for scatters, matching
``lax.all_gather``'s major-to-minor concatenation order — on hardware this
is also the right order (intra-pod ICI stage before the cross-pod DCN
stage, cf. MegaScale).
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import dataclasses
import functools
import math
import weakref

import jax
import jax.numpy as jnp

from repro.compat import axis_size
from repro.core import overlap
from repro.core.telemetry import SCOPE_DECODE, SCOPE_ENCODE, SCOPE_MOVE
from repro.core.codecs import (IdentityCodec,  # noqa: F401 — re-exported
                               achieved_wire_bytes, pack_wire, unpack_wire)

Identity = IdentityCodec()


def _axes_tuple(axis_name):
    return axis_name if isinstance(axis_name, tuple) else (axis_name,)


def _pad_to(x, mult):
    n = x.shape[-1]
    rem = (-n) % mult
    if rem:
        pad = [(0, 0)] * (x.ndim - 1) + [(0, rem)]
        x = jnp.pad(x, pad)
    return x, n


# --------------------------------------------------------------------------
# single-buffer wire packing
# --------------------------------------------------------------------------

_WIRE_PACKING: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "repro_wire_packing", default=True)


@contextlib.contextmanager
def multibuffer_wire():
    """Temporarily restore the pre-packing transport engine: each encoded
    component moves as its own collective, and chunked-ring codecs fall
    back to the monolithic transport (the ring exists to slice the packed
    buffer).  Affects TRACING: only use around fresh jit/lower calls
    (parity tests and benchmarks) — already-compiled functions keep
    whatever layout they were traced with.

    The toggle is a :mod:`contextvars` value, not a module global: nested
    uses restore the exact enclosing state on exit (token-based reset),
    and concurrent contexts — threaded test runners, async drivers —
    each see their own value, so one test's multibuffer window can never
    leak transport mode into another."""
    token = _WIRE_PACKING.set(False)
    try:
        yield
    finally:
        _WIRE_PACKING.reset(token)


def _wire_layout(codec, n):
    wl = getattr(codec, "wire_layout", None)
    return None if wl is None else wl(n)


# --------------------------------------------------------------------------
# slot renegotiation: negotiated widths, truncation, achieved-bytes probes
# --------------------------------------------------------------------------

#: Live SlotControllers (weak: a dropped controller needs no unregister).
#: Probe callbacks fan observations out to every registered controller;
#: with none registered the probes are inert.
_CONTROLLERS: "weakref.WeakSet[SlotController]" = weakref.WeakSet()


def _slot_key(codec):
    """The codec with any negotiated ``moved_frac`` stripped — the stable
    identity a controller tracks stats under (and the static-bound
    variant a resync step runs against)."""
    if getattr(codec, "moved_frac", None) is not None:
        return dataclasses.replace(codec, moved_frac=None)
    return codec


def negotiated_wire_bytes(codec, n: int, *, chunk: int | None = None):
    """Static MOVED byte width of one hop's wire buffer for an
    ``n``-element slot under the codec's negotiated ``moved_frac``, or
    None when the full slot bound moves (static layouts, un-negotiated
    codecs).  ``chunk`` selects the ring chunk's fraction; ``chunk=None``
    is a monolithic hop, which must cover every chunk's payload and so
    takes the max fraction.  The width is clamped to the layout's
    always-achieved floor (every component before the trailing data
    region — a wire is never narrower than its header + metadata) and to
    the slot bound."""
    layout = _wire_layout(codec, n)
    if layout is None or not layout.variable:
        return None
    frac = getattr(codec, "moved_frac", None)
    if frac is None:
        return None
    f = max(frac) if chunk is None else frac[min(chunk, len(frac) - 1)]
    floor = layout.components[-1].offset
    return max(floor, min(layout.total_bytes,
                          math.ceil(layout.total_bytes * f)))


def _zero_repad(wire, total_bytes: int):
    """Widen a truncated wire buffer back to the full slot bound with
    zero bytes — the exact inverse of the truncation whenever the slot's
    achieved bytes fit the moved width (variable layouts zero everything
    past the achieved length, so the dropped tail WAS zero)."""
    pad = total_bytes - wire.shape[-1]
    if pad <= 0:
        return wire
    return jnp.pad(wire, [(0, 0)] * (wire.ndim - 1) + [(0, pad)])


def _dispatch_probe(key, slot_bytes, moved_bytes, chunk, achieved):
    """Host side of an achieved-bytes probe (runs via jax.debug.callback,
    possibly on a runtime thread): enqueue on every live controller.
    Appends to thread-safe deques only — controllers aggregate later,
    under ``jax.effects_barrier`` in ``finish_step``."""
    ach = int(achieved)
    for ctl in list(_CONTROLLERS):
        ctl._obs.append((key, chunk, slot_bytes, moved_bytes, ach))


def _slot_probe(codec, layout, wire, moved_bytes: int, chunk: int) -> None:
    """Emit one achieved-bytes observation for a hop's encoded wire (max
    over the slot rows) when the codec opted into slot renegotiation.
    The callback is an ordered effect OUTSIDE the jit dataflow — it adds
    no collective and cannot perturb bit-parity; codecs with
    ``slot="static"`` (the default) trace zero probes."""
    if not layout.variable or getattr(codec, "slot", "static") != "auto":
        return
    mx = jnp.max(achieved_wire_bytes(wire, layout))
    jax.debug.callback(
        functools.partial(_dispatch_probe, _slot_key(codec),
                          int(layout.total_bytes), int(moved_bytes),
                          int(chunk)), mx)


# --------------------------------------------------------------------------
# error escalation: sampled relative-quantization-error probes
# --------------------------------------------------------------------------

#: Live ErrorEscalationControllers (repro.core.policy) — weak, like
#: :data:`_CONTROLLERS`; with none registered the probes are inert.
_ERR_CONTROLLERS: "weakref.WeakSet" = weakref.WeakSet()


def _dispatch_err_probe(key, err):
    """Host side of a relative-error probe (jax.debug.callback, possibly
    a runtime thread): enqueue on every live escalation controller.
    Thread-safe deque appends only — controllers aggregate later, under
    ``jax.effects_barrier`` in ``finish_step``."""
    e = float(err)
    for ctl in list(_ERR_CONTROLLERS):
        ctl._obs.append((key, e))


def _err_probe(codec, x2d, wire, n: int) -> None:
    """Emit one SAMPLED relative-quantization-error observation for a
    hop's encoded wire when the codec carries an ``escalate=`` policy:
    decode the first wire row back on device and stream
    ``||dec - x|| / ||x||`` to the live ErrorEscalationControllers
    (``repro.core.policy``) through the same ordered-effect callback
    channel as the achieved-bytes probes — no collective, no dataflow
    perturbation.  Codecs without the token (the default) trace ZERO
    probe ops, keeping their lowered HLO byte-identical."""
    if getattr(codec, "escalate", None) is None:
        return
    ref = x2d[:1].astype(jnp.float32)
    dec = codec.decode_wire(wire[:1], n, jnp.float32)
    err = jnp.sqrt(jnp.sum((dec - ref) ** 2)) \
        / (jnp.sqrt(jnp.sum(ref * ref)) + 1e-12)
    jax.debug.callback(
        functools.partial(_dispatch_err_probe, _slot_key(codec)), err)


def _transport(x2d, codec, move, *, reduce=False, dtype):
    """Shared codec plumbing for every compressed collective: pad the
    trailing dim of ``x2d`` to the codec granule, encode straight into the
    packed uint8 wire buffer (``encode_wire`` — one fused kernel write on
    the interpret impl), apply ``move`` (ONE lax collective), and decode
    straight from the moved buffer — fused-summing the stacked peer axis
    when ``reduce`` — then crop the padding.  Codecs without a wire
    layout (or under :func:`multibuffer_wire`) fall back to one ``move``
    per encoded component.

    Negotiated-slot codecs move only ``negotiated_wire_bytes`` of the
    bound: the wire is truncated before ``move`` and zero-repadded after
    (bit-exact under the variable-layout zero-tail contract; the achieved
    probe feeds the controller's overflow/resync protocol), still exactly
    one lax collective."""
    padded, n = _pad_to(x2d, codec.granule)
    pn = padded.shape[-1]
    layout = _wire_layout(codec, pn) if _WIRE_PACKING.get() else None
    if layout is None:
        with jax.named_scope(SCOPE_ENCODE):
            enc = codec.encode(padded)
        with jax.named_scope(SCOPE_MOVE):
            enc = tuple(move(a) for a in enc)
        with jax.named_scope(SCOPE_DECODE):
            if reduce:
                return codec.decode_sum(enc, pn, dtype)[:n]
            return codec.decode(enc, pn, dtype)[..., :n]
    moved_b = negotiated_wire_bytes(codec, pn, chunk=None)
    with jax.named_scope(SCOPE_ENCODE):
        wire = codec.encode_wire(padded)
        _slot_probe(codec, layout, wire,
                    layout.total_bytes if moved_b is None else moved_b, 0)
        _err_probe(codec, padded, wire, pn)
    with jax.named_scope(SCOPE_MOVE):
        if moved_b is not None and moved_b < layout.total_bytes:
            wire = _zero_repad(move(wire[..., :moved_b]),
                               layout.total_bytes)
        else:
            wire = move(wire)
    with jax.named_scope(SCOPE_DECODE):
        if reduce:
            return codec.decode_sum_wire(wire, pn, dtype)[:n]
        return codec.decode_wire(wire, pn, dtype)[..., :n]


def _compressed_collective(name, impl, bwd, n_static, doc=None):
    """Build one compressed collective with a straight-through custom_vjp.

    ``impl(x, *static)`` runs the forward communication (static ends with
    the ``(fwd_codec, bwd_codec)`` pair); ``bwd(ct, *static)`` routes the
    cotangent through the conjugate collective with the codecs swapped.
    All ``n_static`` trailing args are nondiff (axis names, dims/perms,
    codecs) so they stay Python values under tracing.
    """
    @functools.partial(jax.custom_vjp,
                       nondiff_argnums=tuple(range(1, n_static + 1)))
    def op(x, *static):
        return impl(x, *static)

    def _fwd(x, *static):
        return impl(x, *static), None

    def _bwd(*args):
        static, ct = args[:n_static], args[-1]
        return (bwd(ct, *static),)

    op.defvjp(_fwd, _bwd)
    op.__name__ = op.__qualname__ = name
    if doc:
        op.__doc__ = doc
    return op


# --------------------------------------------------------------------------
# forward impls (shared by the custom_vjp wrappers below)
# --------------------------------------------------------------------------

def _ring_chunks(codec):
    """Number of ring chunks the codec requests (1 = monolithic).

    Codecs without the knob (``IdentityCodec``) count as 1 — the ring
    exists to slice the packed wire buffer, which they don't have."""
    return int(getattr(codec, "chunks", 1) or 1)


def _peer_order(stack, idx, p):
    """Reorder an arrival-ordered ``(P, ...)`` stack into peer-index order.

    THE ring bit-parity invariant.  After k neighbor-forwarding hops a
    device holds the buffer of peer ``(idx - k) mod P``, so arrivals are
    stacked in a device-DEPENDENT order; the monolithic collectives
    (``lax.all_gather`` / the two-shot all-to-all) deliver peer-index
    order on every device.  Decoding — and especially ``decode_sum``'s
    sequential float accumulation, whose rounding depends on operand
    order — must therefore consume ``stack[j] == peer j's buffer``
    everywhere, which this gather restores (peer j's buffer sits at
    arrival ``(idx - j) mod P``).  Skipping it would yield per-device
    1-ulp sum differences, not just permuted outputs."""
    return jnp.take(stack, (idx - jnp.arange(p)) % p, axis=0)


def _chunk_slices(x2d, codec):
    """Pad the trailing dim to ``chunks * granule`` and return the static
    chunk views plus the original trailing size and chunk size.

    The padding is compressed and shipped like real data (see
    ``wire_slot_bytes`` for the byte accounting); every chunk view has
    the same static size so all ring streams share one wire layout."""
    chunks = _ring_chunks(codec)
    padded, n0 = _pad_to(x2d, chunks * codec.granule)
    csz = padded.shape[-1] // chunks
    return [padded[:, c * csz:(c + 1) * csz] for c in range(chunks)], n0, csz


def _ag_one_ring(x, ax, dim, codec):
    """Chunked ring all-gather: the local wire buffer is forwarded
    neighbor-to-neighbor for P-1 ``ppermute`` steps per chunk, and each
    chunk's decode consumes the peer-ordered arrival stack (see
    :func:`_peer_order` for the invariant), making the result
    bit-identical to the monolithic single-collective path.

    Chunk streams are data-independent, so chunk c+1's encode and chunk
    c-1's fused decode can overlap chunk c's transfer; the stage emission
    order (pipelined with barrier fences vs hoisted serial) is the
    codec's ``schedule`` knob, dispatched through
    :func:`repro.core.overlap.run_ring`.

    Negotiated-slot codecs make the ring RAGGED-AWARE: chunk ``c``'s
    encode truncates its wire to ``negotiated_wire_bytes(..., chunk=c)``
    (per-chunk achieved-byte mass, not an equal slot split), its
    ``p-1`` ppermutes move the truncated buffer, and its decode
    zero-repads before the usual wire decode — per-chunk stage closures
    through :func:`overlap.run_ring`'s FIFO pairing, chunk ELEMENT
    boundaries unchanged, bit-parity via the zero-tail contract."""
    p = axis_size(ax)
    segs, n0, csz = _chunk_slices(x.reshape(1, -1), codec)
    layout = _wire_layout(codec, csz)
    total = layout.total_bytes
    moved = [negotiated_wire_bytes(codec, csz, chunk=c)
             for c in range(len(segs))]
    ring = tuple((s, (s + 1) % p) for s in range(p))
    idx = jax.lax.axis_index(ax)

    @jax.named_scope(SCOPE_MOVE)
    def transfer(buf):
        """P-1 neighbor-forwarding ring steps -> peer-ordered stack."""
        arrivals = [buf]
        for _ in range(p - 1):
            buf = jax.lax.ppermute(buf, ax, ring)
            arrivals.append(buf)
        return _peer_order(jnp.stack(arrivals)[:, 0], idx, p)   # (P, bytes)

    def enc_for(c):
        @jax.named_scope(SCOPE_ENCODE)
        def enc(seg):
            wire = codec.encode_wire(seg)
            m = moved[c]
            _slot_probe(codec, layout, wire, total if m is None else m, c)
            if c == 0:   # sampled: one error probe per ring hop
                _err_probe(codec, seg, wire, csz)
            return wire if m is None or m >= total else wire[..., :m]
        return enc

    def dec_for(c):
        @jax.named_scope(SCOPE_DECODE)
        def dec(stack):
            if moved[c] is not None and moved[c] < total:
                stack = _zero_repad(stack, total)
            return codec.decode_wire(stack, csz, x.dtype)
        return dec

    outs = overlap.run_ring(
        segs, encode=[enc_for(c) for c in range(len(segs))],
        transfer=transfer,
        decode=[dec_for(c) for c in range(len(segs))],
        schedule=overlap.ring_schedule(codec))
    dec = (jnp.concatenate(outs, axis=-1) if len(outs) > 1
           else outs[0])[:, :n0]                                  # (P, n)
    dec = dec.reshape(p, *x.shape)
    out = jnp.moveaxis(dec, 0, dim)
    shape = list(x.shape)
    shape[dim] *= p
    return out.reshape(shape)


def _rs_one_ring(x, ax, dim, codec):
    """Chunked ring reduce-scatter (two-shot preserving): at step k every
    device ppermutes its once-compressed contribution for the peer k hops
    ahead directly to it — no partial-sum requantization — and the fused
    ``decode_sum`` runs per chunk on the peer-ordered stack (see
    :func:`_peer_order`), bit-identical to the monolithic compressed
    all-to-all.  Stage emission order is the codec's ``schedule`` knob,
    dispatched through :func:`repro.core.overlap.run_ring`.

    The per-peer sends are hoisted OUT of the step loop as one gather of
    the chunk's (P, bytes) wire matrix into send order (row k = the
    contribution for the peer k hops ahead); each step then reads its row
    with a static slice.  The former per-step ``dynamic_index_in_dim``
    selections re-materialized a dynamic-slice of the full wire matrix at
    every step — the lowered HLO now carries ZERO dynamic-slices
    (asserted in tests/multidev/check_parity.py), bit-parity unchanged.
    """
    p = axis_size(ax)
    rowsrc = jnp.moveaxis(x, dim, 0)
    d = rowsrc.shape[0]
    if d % p:
        raise ValueError(
            f"compressed reduce-scatter: scatter dim {dim} has size {d}, "
            f"not divisible by axis {ax!r} of size {p}")
    rows = rowsrc.reshape(p, -1)                   # row j -> destined peer j
    segs, n0, csz = _chunk_slices(rows, codec)
    layout = _wire_layout(codec, csz)
    total = layout.total_bytes
    moved = [negotiated_wire_bytes(codec, csz, chunk=c)
             for c in range(len(segs))]
    idx = jax.lax.axis_index(ax)

    @jax.named_scope(SCOPE_MOVE)
    def transfer(wire):
        """Shifted two-shot sends -> peer-ordered stack, one hoisted
        gather: ``sends[k] == wire[(idx + k) % p]``."""
        sends = jnp.take(wire, (idx + jnp.arange(p)) % p, axis=0)
        arrivals = [sends[0]]                      # own contribution
        for k in range(1, p):
            shift = tuple((s, (s + k) % p) for s in range(p))
            arrivals.append(jax.lax.ppermute(sends[k], ax, shift))
        return _peer_order(jnp.stack(arrivals), idx, p)        # (P, bytes)

    def enc_for(c):
        @jax.named_scope(SCOPE_ENCODE)
        def enc(seg):
            wire = codec.encode_wire(seg)
            m = moved[c]
            _slot_probe(codec, layout, wire, total if m is None else m, c)
            if c == 0:   # sampled: one error probe per ring hop
                _err_probe(codec, seg, wire, csz)
            return wire if m is None or m >= total else wire[..., :m]
        return enc

    def dec_for(c):
        @jax.named_scope(SCOPE_DECODE)
        def dec(stack):
            if moved[c] is not None and moved[c] < total:
                stack = _zero_repad(stack, total)
            out = codec.decode_sum_wire(stack, csz, x.dtype)
            return out.reshape(-1)[:csz]
        return dec

    outs = overlap.run_ring(
        segs, encode=[enc_for(c) for c in range(len(segs))],
        transfer=transfer,
        decode=[dec_for(c) for c in range(len(segs))],
        schedule=overlap.ring_schedule(codec))
    summed = (jnp.concatenate(outs) if len(outs) > 1 else outs[0])[:n0]
    out = summed.reshape(d // p, *rowsrc.shape[1:])
    return jnp.moveaxis(out, 0, dim) if dim != 0 else out


def _ag_one(x, ax, dim, codec):
    """One-axis compressed all-gather: identity codecs take the native
    lax collective (baseline HLO untouched), chunked wire codecs the
    ring, everything else the monolithic packed transport — all three
    bit-identical (check_parity matrix)."""
    if isinstance(codec, IdentityCodec):
        return jax.lax.all_gather(x, ax, axis=dim, tiled=True)
    if _WIRE_PACKING.get() and _ring_chunks(codec) > 1 \
            and _wire_layout(codec, codec.granule):
        return _ag_one_ring(x, ax, dim, codec)
    p = axis_size(ax)
    dec = _transport(
        x.reshape(1, -1), codec,
        lambda a: jax.lax.all_gather(a, ax, axis=0, tiled=False)[:, 0],
        dtype=x.dtype)                                        # (P, n)
    dec = dec.reshape(p, *x.shape)
    out = jnp.moveaxis(dec, 0, dim)                           # (..., P, d, ...)
    shape = list(x.shape)
    shape[dim] *= p
    return out.reshape(shape)


def _ag_impl(x, axis_name, dim, codec):
    """Hierarchical all-gather over (possibly tuple) ``axis_name``,
    innermost axis first — matches ``lax.all_gather``'s major-to-minor
    concatenation order (module docstring)."""
    for ax in reversed(_axes_tuple(axis_name)):
        x = _ag_one(x, ax, dim, codec)
    return x


def _rs_one(x, ax, dim, codec):
    """One-axis compressed reduce-scatter (same three-way dispatch as
    :func:`_ag_one`); the compressed path is the paper's two-shot: ONE
    compressed all-to-all + ONE fused local reduction, no partial-sum
    requantization."""
    if isinstance(codec, IdentityCodec):
        return jax.lax.psum_scatter(x, ax, scatter_dimension=dim, tiled=True)
    if _WIRE_PACKING.get() and _ring_chunks(codec) > 1 \
            and _wire_layout(codec, codec.granule):
        return _rs_one_ring(x, ax, dim, codec)
    p = axis_size(ax)
    moved = jnp.moveaxis(x, dim, 0)
    d = moved.shape[0]
    if d % p:
        # a ValueError, not an assert: `python -O` strips asserts and the
        # reshape below would silently mis-slice peers into bit-garbage
        raise ValueError(
            f"compressed reduce-scatter: scatter dim {dim} has size {d}, "
            f"not divisible by axis {ax!r} of size {p}")
    chunks = moved.reshape(p, -1)                              # chunk i -> peer i
    # Paper's two-shot phase 1: ONE compressed AlltoAll, followed by ONE
    # fused local reduction (rotated-domain, single inverse rotation —
    # DESIGN.md §7.2).
    summed = _transport(
        chunks, codec,
        lambda a: jax.lax.all_to_all(a, ax, split_axis=0, concat_axis=0,
                                     tiled=False),
        reduce=True, dtype=x.dtype)
    out = summed.reshape(d // p, *moved.shape[1:])
    return jnp.moveaxis(out, 0, dim) if dim != 0 else out


def _rs_impl(x, axis_name, dim, codec):
    """Hierarchical reduce-scatter, outermost axis first (the scatter
    conjugate of :func:`_ag_impl`'s gather order)."""
    for ax in _axes_tuple(axis_name):
        x = _rs_one(x, ax, dim, codec)
    return x


def _ar_impl(x, axis_name, codec):
    """Compressed two-shot AllReduce = ReduceScatter ∘ AllGather over the
    flattened tensor (two compressions per round, as in the paper);
    identity codecs take native ``lax.psum``."""
    if isinstance(codec, IdentityCodec):
        return jax.lax.psum(x, axis_name)
    axes = _axes_tuple(axis_name)
    ptot = 1
    for ax in axes:
        ptot *= axis_size(ax)
    flat, n = _pad_to(x.reshape(1, -1), ptot * codec.granule)
    flat = flat[0]
    rs = _rs_impl(flat, axis_name, 0, codec)
    ag = _ag_impl(rs, axis_name, 0, codec)
    return ag[:n].reshape(x.shape)


def _pp_impl(x, axis_name, perm, codec):
    """Compressed point-to-point permute: one packed wire buffer per
    ``lax.ppermute``.  ``chunks=`` is deliberately ignored here — a
    pipeline send is already a single hop with nothing to ring over
    (telemetry accounts accordingly, see ``wire_slot_bytes``)."""
    if isinstance(codec, IdentityCodec):
        return jax.lax.ppermute(x, axis_name, perm)
    dec = _transport(x.reshape(1, -1), codec,
                     lambda a: jax.lax.ppermute(a, axis_name, perm),
                     dtype=x.dtype)
    return dec[0].reshape(x.shape)


def _a2a_impl(x, axis_name, split_dim, concat_dim, codec):
    """Compressed all-to-all (MoE dispatch / the Ulysses sp hop), one
    packed wire buffer per hop; the received peer blocks are reassembled
    peer-major along ``concat_dim`` while ``split_dim`` shrinks by the
    axis size — reproducing the tiled ``lax.all_to_all`` layout
    bit-for-bit for BOTH the equal-dims (MoE) and transposed
    (``split_dim != concat_dim``, Ulysses heads<->sequence) cases.
    ``chunks=`` ignored, as for ppermute."""
    if isinstance(codec, IdentityCodec):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=split_dim, concat_axis=concat_dim, tiled=True)
    p = axis_size(axis_name)
    moved = jnp.moveaxis(x, split_dim, 0)
    d = moved.shape[0]
    if d % p:
        raise ValueError(
            f"compressed all-to-all: split dim {split_dim} has size {d}, "
            f"not divisible by axis {axis_name!r} of size {p}")
    chunks = moved.reshape(p, -1)
    dec = _transport(
        chunks, codec,
        lambda a: jax.lax.all_to_all(a, axis_name, split_axis=0,
                                     concat_axis=0, tiled=False),
        dtype=x.dtype)
    # stack[j] = peer j's split block, shaped like the local block with
    # split_dim already shrunk to d/p and moved to the front
    stack = dec.reshape(p, d // p, *moved.shape[1:])
    # undo the moveaxis inside each peer block, then insert the peer axis
    # just before concat_dim and merge (peer-major) — exactly the tiled
    # layout: concat_dim grows p-fold, split_dim shrinks p-fold (for
    # split_dim == concat_dim the two compose back to size d)
    blocks = jnp.moveaxis(stack, 1, split_dim + 1)
    out = jnp.moveaxis(blocks, 0, concat_dim)
    shape = list(x.shape)
    shape[split_dim] = d // p
    shape[concat_dim] *= p
    return out.reshape(shape)


# --------------------------------------------------------------------------
# the public collectives: conjugate (impl, bwd) pairs of the one wrapper
# --------------------------------------------------------------------------

all_gather_c = _compressed_collective(
    "all_gather_c",
    impl=lambda x, axis_name, dim, fc, bc: _ag_impl(x, axis_name, dim, fc),
    bwd=lambda ct, axis_name, dim, fc, bc:
        psum_scatter_c(ct, axis_name, dim, bc, fc),
    n_static=4,
    doc="""Compressed all-gather concatenating along ``dim`` (tiled layout).

    ``all_gather_c(x, axis_name, dim, fwd_codec, bwd_codec)``; backward is
    the compressed reduce-scatter with the codec pair swapped.

    Wire/parity contract: one packed uint8 wire buffer per lax collective
    (``chunks*(P-1)`` ppermutes on the ring path, schedule per the
    codec's ``schedule`` knob); output matches the tiled
    ``lax.all_gather`` layout and is bit-identical across the packed /
    multibuffer / ring-pipelined / ring-serial transports for every
    registered codec (tests/multidev/check_parity.py).""")


psum_scatter_c = _compressed_collective(
    "psum_scatter_c",
    impl=lambda x, axis_name, dim, fc, bc: _rs_impl(x, axis_name, dim, fc),
    bwd=lambda ct, axis_name, dim, fc, bc:
        all_gather_c(ct, axis_name, dim, bc, fc),
    n_static=4,
    doc="""Compressed reduce-scatter along ``dim`` (tiled layout).

    ``psum_scatter_c(x, axis_name, dim, fwd_codec, bwd_codec)``; backward
    is the compressed all-gather with the codec pair swapped.

    Wire/parity contract: two-shot — every contribution is compressed
    exactly ONCE (no partial-sum requantization) and the fused
    ``decode_sum`` accumulates the peer stack in peer-index order on
    every device (:func:`_peer_order`), so packed / multibuffer /
    ring-pipelined / ring-serial transports are bit-identical; the
    scatter dim must divide by the axis size (ValueError otherwise).""")


allreduce_g = _compressed_collective(
    "allreduce_g",
    impl=lambda x, axis_name, fc, bc: _ar_impl(x, axis_name, fc),
    bwd=lambda ct, axis_name, fc, bc: ct,
    n_static=3,
    doc="""Megatron "g": forward compressed two-shot AllReduce, backward
    identity. Use at row-parallel outputs (non-SP TP mode / decode).

    Wire/parity contract: lowers to ReduceScatter ∘ AllGather over the
    flattened tensor — both hops inherit the full transport matrix
    (packing, ring schedules, bit-identity) of the underlying
    collectives; identity codecs lower to native ``lax.psum``.""")


copy_f = _compressed_collective(
    "copy_f",
    impl=lambda x, axis_name, fc, bc: x,
    bwd=lambda ct, axis_name, fc, bc: _ar_impl(ct, axis_name, bc),
    n_static=3,
    doc="""Megatron "f": forward identity, backward compressed AllReduce.
    Use at column-parallel inputs (non-SP TP mode).

    Wire/parity contract: the forward emits NO collective; the backward
    AllReduce uses the BACKWARD codec (cotangent compression is
    straight-through, as in the paper) and inherits ``allreduce_g``'s
    transport contract.""")


ppermute_c = _compressed_collective(
    "ppermute_c",
    impl=lambda x, axis_name, perm, fc, bc: _pp_impl(x, axis_name, perm, fc),
    bwd=lambda ct, axis_name, perm, fc, bc:
        ppermute_c(ct, axis_name, tuple((d, s) for s, d in perm), bc, fc),
    n_static=4,
    doc="""Compressed point-to-point send (pipeline boundaries; TahQuant
    compression site). ``perm`` is a tuple of (src, dst) pairs, as
    lax.ppermute; backward routes through the inverted permutation.

    Wire/parity contract: exactly ONE ``lax.ppermute`` moving the packed
    wire buffer per hop — ``chunks=`` is ignored (a point-to-point send
    has nothing to ring over) and telemetry counts granule-only
    padding.""")


all_to_all_c = _compressed_collective(
    "all_to_all_c",
    impl=lambda x, axis_name, split_dim, concat_dim, fc, bc:
        _a2a_impl(x, axis_name, split_dim, concat_dim, fc),
    bwd=lambda ct, axis_name, split_dim, concat_dim, fc, bc:
        all_to_all_c(ct, axis_name, concat_dim, split_dim, bc, fc),
    n_static=5,
    doc="""Compressed all-to-all (MoE expert-parallel dispatch; the paper's
    compressed AlltoAll; the Ulysses sequence-parallel redistribute).
    Backward swaps split/concat dims and codecs — for the transposed
    Ulysses hop that conjugate is exactly the inverse redistribute, so
    straight-through cotangent compression falls out of the swap.

    Wire/parity contract: ONE ``lax.all_to_all`` moving the packed wire
    buffer; output reproduces the tiled native layout bit-for-bit for
    both ``split_dim == concat_dim`` and the transposed
    ``split_dim != concat_dim`` case; the split dim must divide by the
    axis size (ValueError otherwise); ``chunks=`` ignored.""")


def psum_exact(x, axis_name):
    """psum whose backward passes the (replicated) cotangent through
    unchanged — the mathematically correct transpose when every consumer of
    the summed value is replicated over ``axis_name`` (scalar losses,
    softmax statistics). Avoids the psum->psum transpose inflation that
    shard_map applies under check_vma=False."""
    return allreduce_g(x, axis_name, Identity, Identity)


# --------------------------------------------------------------------------
# Communication-volume accounting (for benchmarks / roofline cross-check)
# --------------------------------------------------------------------------

def wire_slot_bytes(codec, n: int, *, chunks: int | None = None):
    """EXACT packed-buffer bytes the transport puts on the wire for one
    ``n``-element slot: the trailing dim is padded to ``chunks * granule``
    (matching ``_pad_to``/``_chunk_slices``) and each of the ``chunks``
    wire slices is ``wire_layout(padded / chunks).total_bytes`` — the
    telemetry therefore equals the actual uint8 buffer size even for
    ragged trailing dims.  ``chunks`` defaults to the codec's ring chunk
    count (the AG/RS transports); pass ``chunks=1`` for hops that never
    chunk (ppermute / all-to-all route chunked codecs through the
    monolithic transport).  Returns None for layout-less codecs
    (identity: raw dtype bytes, no padding).

    For variable (bounded-but-ragged) layouts this is the SLOT bound —
    the static buffer size the lax collective actually moves.  The
    data-dependent achieved bytes of a concrete tensor are
    :func:`achieved_slot_bytes`."""
    chunks = _ring_chunks(codec) if chunks is None else max(1, int(chunks))
    mult = chunks * codec.granule
    padded = ((int(n) + mult - 1) // mult) * mult
    layout = _wire_layout(codec, padded // chunks)
    if layout is None:
        return None
    return chunks * layout.total_bytes


def moved_slot_bytes(codec, n: int, *, chunks: int | None = None):
    """EXACT bytes the transport MOVES for one ``n``-element slot under
    the codec's negotiated ``moved_frac`` — the per-chunk
    :func:`negotiated_wire_bytes` widths summed over the ring chunks
    (``chunks`` defaults as for :func:`wire_slot_bytes`).  Equals
    ``wire_slot_bytes`` for static layouts and un-negotiated codecs;
    None for layout-less codecs.  Sits strictly between
    :func:`achieved_slot_bytes` (the payload) and
    :func:`wire_slot_bytes` (the bound) on every overflow-free step."""
    chunks = _ring_chunks(codec) if chunks is None else max(1, int(chunks))
    mult = chunks * codec.granule
    padded = ((int(n) + mult - 1) // mult) * mult
    csz = padded // chunks
    layout = _wire_layout(codec, csz)
    if layout is None:
        return None
    if chunks == 1:
        m = negotiated_wire_bytes(codec, csz, chunk=None)
        return layout.total_bytes if m is None else m
    total = 0
    for c in range(chunks):
        m = negotiated_wire_bytes(codec, csz, chunk=c)
        total += layout.total_bytes if m is None else m
    return total


def achieved_slot_bytes(codec, x2d, *, chunks: int | None = None):
    """ACHIEVED (data-dependent) wire bytes per slot row of ``x2d``.

    Mirrors the transport exactly: the trailing dim is padded to
    ``chunks * granule`` (as ``_chunk_slices``), each chunk slice is
    encoded through ``encode_wire``, and the per-slot achieved widths
    (:func:`repro.core.codecs.achieved_wire_bytes` — length headers on
    variable layouts, the full slot width on static ones) are summed
    over chunks.  Returns a ``(slots,)`` uint32-ish array, or None for
    layout-less codecs.  For static layouts every entry equals
    ``wire_slot_bytes(codec, n, chunks=chunks)``; for variable layouts
    entries are <= that bound — the gap is what a ragged-aware fabric
    (or the achieved-ratio benchmark rows) gets to claim.

    Runs the codec's encode on device — telemetry/benchmark use, not a
    free static lookup like :func:`wire_slot_bytes`."""
    chunks = _ring_chunks(codec) if chunks is None else max(1, int(chunks))
    mult = chunks * codec.granule
    padded, _ = _pad_to(x2d, mult)
    csz = padded.shape[-1] // chunks
    layout = _wire_layout(codec, csz)
    if layout is None:
        return None
    total = None
    for c in range(chunks):
        wire = codec.encode_wire(padded[:, c * csz:(c + 1) * csz])
        ach = achieved_wire_bytes(wire, layout)
        total = ach if total is None else total + ach
    return total


def _achieved_total(codec, sample, chunks=None):
    """Summed achieved bytes of ``sample``'s slot rows, or None when the
    codec has no layout (callers then fall back to the static bound)."""
    ach = achieved_slot_bytes(codec, sample, chunks=chunks)
    return None if ach is None else float(jnp.sum(ach))


def gather_wire_bytes(local_shape, dtype, p, codec, *, sample=None) -> float:
    """Exact bytes put on the wire per device by one all_gather (the
    local slot's packed wire buffer, including chunk padding, replicated
    to the other p-1 peers).

    With ``sample`` (a local tensor of ``local_shape``) the ACHIEVED
    bytes of that data are reported instead of the slot bound — equal
    for static layouts, <= for variable ones."""
    import numpy as np
    n = int(np.prod(local_shape))
    if sample is not None:
        ach = _achieved_total(codec, sample.reshape(1, -1))
        if ach is not None:
            return ach * (p - 1)
    slot = wire_slot_bytes(codec, n)
    if slot is None:
        slot = n * np.dtype(dtype).itemsize
    return float(slot) * (p - 1)


def scatter_wire_bytes(local_shape, dtype, p, codec, *, sample=None) -> float:
    """Exact bytes put on the wire per device by one reduce-scatter:
    p-1 of the p destination slots (each ``n/p`` elements, padded and
    packed) leave the device.

    With ``sample`` the ACHIEVED bytes are reported: the sample's rows
    are split into the p destination slots exactly as the transport does
    and the per-slot achieved widths summed, scaled by (p-1)/p (which of
    the p slots stays home is device-dependent; the scale is exact for
    static layouts and the peer-average for ragged ones)."""
    import numpy as np
    n = int(np.prod(local_shape))
    if sample is not None and n % p == 0:
        ach = _achieved_total(codec, sample.reshape(p, -1))
        if ach is not None:
            return ach * (p - 1) / p
    slot = wire_slot_bytes(codec, n // p)
    if slot is None:
        slot = (n // p) * np.dtype(dtype).itemsize
    return float(slot) * (p - 1)


def a2a_wire_bytes(local_shape, dtype, p, codec, *, sample=None) -> float:
    """Exact bytes put on the wire per device by one all-to-all: p-1 of
    the p split slots (each ``n/p`` elements, padded and packed,
    ``chunks=1`` — the a2a transport never rings) leave the device.
    ``sample`` reports achieved bytes, scaled (p-1)/p as for
    :func:`scatter_wire_bytes`."""
    import numpy as np
    n = int(np.prod(local_shape))
    if sample is not None and n % p == 0:
        ach = _achieved_total(codec, sample.reshape(p, -1), chunks=1)
        if ach is not None:
            return ach * (p - 1) / p
    slot = wire_slot_bytes(codec, n // p, chunks=1)
    if slot is None:
        slot = (n // p) * np.dtype(dtype).itemsize
    return float(slot) * (p - 1)


# --------------------------------------------------------------------------
# SlotController: adaptive slot renegotiation (host side, between steps)
# --------------------------------------------------------------------------

class SlotController:
    """Host-side renegotiation protocol for ``slot="auto"`` wire codecs.

    Per negotiated codec identity (:func:`_slot_key` — the codec with
    ``moved_frac`` stripped) the controller runs a two-state protocol::

        STATIC ──(watermark known)──> NEGOTIATED(frac)
           ^                              │
           └──(overflow: achieved > moved, one-step resync)──┘

    * In STATIC (bootstrap, or the step after an overflow) hops move the
      full slot bound — always bit-exact — while their probes record
      achieved bytes.
    * In NEGOTIATED hops move ``ceil(frac * bound)`` where ``frac`` is
      the decaying achieved/slot high-watermark times ``1 + headroom``
      (the codec's ``headroom`` field), rounded UP to the 1/32
      :data:`QUANTUM` grid — quantization keeps the set of traced wire
      widths (and therefore jit cache entries) small and bounded.
    * A probe observing ``achieved > moved`` is an OVERFLOW: that step's
      decode may have dropped nonzero tail bytes, so ``finish_step``
      returns True and the caller must DISCARD the step's outputs and
      replay it — ``apply``/``negotiate`` now hand back the static-bound
      variant (one-step resync), and the raised watermark renegotiates a
      wider fraction afterwards.  Never lossy, never deadlocked: the
      static bound can never overflow, so a replay always lands.

    Drive it like the trainer's warmup resolution — entirely outside
    jit::

        ctl = SlotController(reporter=reporter)
        while training:
            plan = ctl.apply(base_plan)        # negotiated codecs
            out = step_fns[plan](state, batch) # donate=False: replayable
            if ctl.finish_step():              # overflow -> resync replay
                plan = ctl.apply(base_plan)    # static-bound variant
                out = step_fns[plan](state, batch)
                ctl.finish_step()

    Thread-safety: probes append to a ``collections.deque`` from the
    runtime's callback threads; ``finish_step`` flushes outstanding
    effects (``jax.effects_barrier``) before draining, so a step's
    probes are fully visible to its own ``finish_step``.
    """

    #: StepController protocol (repro.core.policy): an overflow demands a
    #: bit-exact replay, so consumers must not donate input buffers.
    may_replay = True
    #: Negotiated fractions snap UP to this grid (bounded retrace count).
    QUANTUM = 1.0 / 32.0
    #: High-watermark decay per observation: ``max(obs, d*wm + (1-d)*obs)``
    #: — rises instantly, forgets old spikes over ~1/(1-d) observations.
    DECAY = 0.875

    def __init__(self, reporter=None):
        self.reporter = reporter
        self._obs: collections.deque = collections.deque()
        self._hwm: dict = {}     # (key, chunk) -> achieved/slot frac hwm
        self._frac: dict = {}    # key -> negotiated per-chunk frac tuple
        self._resync: set = set()   # keys pinned to STATIC next step
        self._paths: dict = {}   # key -> set of plan path names (events)
        self.renegotiations = 0
        self.resyncs = 0
        self.overflows = 0
        _CONTROLLERS.add(self)

    # ---- negotiation ------------------------------------------------------
    def negotiate(self, codec):
        """The variant of ``codec`` the next step should run: negotiated
        (``moved_frac`` filled in) once a watermark exists, the
        static-bound key while bootstrapping or resyncing, and any
        non-auto codec unchanged."""
        if getattr(codec, "slot", None) != "auto":
            return codec
        key = _slot_key(codec)
        frac = self._frac.get(key)
        if key in self._resync or frac is None:
            return key
        if getattr(codec, "moved_frac", None) == frac:
            return codec
        return dataclasses.replace(key, moved_frac=frac)

    def apply(self, plan):
        """Per-path :meth:`negotiate` over a CommPlan's codec fields;
        returns the plan unchanged when no path is ``slot="auto"`` (the
        common case costs one getattr per path)."""
        changes = {}
        for f in dataclasses.fields(plan):
            codec = getattr(plan, f.name)
            if getattr(codec, "slot", None) != "auto":
                continue
            self._paths.setdefault(_slot_key(codec), set()).add(f.name)
            neg = self.negotiate(codec)
            if neg is not codec:
                changes[f.name] = neg
        return dataclasses.replace(plan, **changes) if changes else plan

    # ---- observation ingest ----------------------------------------------
    def observe_sample(self, codec, x2d, *, chunks: int | None = None):
        """Record the observations the transport's probes would emit for
        ``x2d`` without running a collective (bench / warm-start path):
        one per-chunk achieved-bytes max at the static slot width,
        mirroring ``_chunk_slices`` on the sample AS GIVEN.

        GEOMETRY CONTRACT: rows of ``x2d`` are taken to be wire rows and
        the trailing dim is chunk-sliced exactly like the packed
        transport's flat view — so feed the layout the transport will
        actually encode (flatten to ``(1, -1)`` for a single-stream
        hop).  The ring transports flatten each device's LOCAL block
        before chunking, which a host-side global sample cannot predict;
        to warm-start those, run one static bootstrap step instead and
        let the runtime probes observe the true per-device geometry
        (tests/multidev/check_parity.py does exactly this)."""
        key = _slot_key(codec)
        if getattr(key, "slot", None) != "auto":
            raise ValueError("observe_sample needs a slot='auto' codec")
        nchunks = _ring_chunks(key) if chunks is None else max(1, int(chunks))
        padded, _ = _pad_to(x2d, nchunks * key.granule)
        csz = padded.shape[-1] // nchunks
        layout = _wire_layout(key, csz)
        for c in range(nchunks):
            wire = key.encode_wire(padded[:, c * csz:(c + 1) * csz])
            ach = int(jnp.max(achieved_wire_bytes(wire, layout)))
            self._obs.append((key, c, int(layout.total_bytes),
                              int(layout.total_bytes), ach))

    # ---- the between-steps protocol tick ----------------------------------
    def finish_step(self) -> bool:
        """Drain this step's probes, update watermarks, and renegotiate.

        Returns True on OVERFLOW: the caller must discard the step's
        outputs and replay the step (``apply`` now returns static-bound
        codecs for the overflowed keys).  Returns False when the step's
        decodes were bit-exact and the next step may run negotiated."""
        jax.effects_barrier()   # flush in-flight probe callbacks
        overflowed: dict = {}
        seen_static: set = set()
        while True:
            try:
                key, chunk, slot_b, moved_b, ach = self._obs.popleft()
            except IndexError:
                break
            f = ach / slot_b
            k = (key, chunk)
            cur = self._hwm.get(k)
            self._hwm[k] = f if cur is None else max(
                f, self.DECAY * cur + (1.0 - self.DECAY) * f)
            if ach > moved_b:
                overflowed[key] = max(overflowed.get(key, 0), ach - moved_b)
            elif moved_b >= slot_b:
                seen_static.add(key)
        if overflowed:
            self.overflows += len(overflowed)
            self.resyncs += len(overflowed)
            self._resync |= set(overflowed)
            for key, by in sorted(overflowed.items(), key=repr):
                self._event("slot/resync", key, overflow_bytes=by)
            return True
        # clean static observations close a resync window: the watermark
        # now covers the spike, so the key may renegotiate again
        self._resync -= seen_static
        self._renegotiate()
        return False

    def _renegotiate(self) -> None:
        per_key: dict = {}
        for (key, chunk), wm in self._hwm.items():
            per_key.setdefault(key, {})[chunk] = wm
        for key, obs in per_key.items():
            if key in self._resync:
                continue
            headroom = float(getattr(key, "headroom", 0.5))
            chunks = _ring_chunks(key)
            # chunks this key never probed at (e.g. only monolithic hops
            # ran so far) borrow the widest observed fraction
            fallback = max(obs.values())
            fracs = tuple(
                self._quantize(obs.get(c, fallback) * (1.0 + headroom))
                for c in range(chunks))
            if fracs != self._frac.get(key):
                self._frac[key] = fracs
                self.renegotiations += 1
                self._event("slot/renegotiate", key,
                            frac_max=max(fracs), frac_min=min(fracs))

    def _quantize(self, f: float) -> float:
        q = math.ceil(f / self.QUANTUM) * self.QUANTUM
        return min(max(q, self.QUANTUM), 1.0)

    # ---- telemetry --------------------------------------------------------
    def _event(self, kind, key, **fields) -> None:
        if self.reporter is not None:
            paths = ",".join(sorted(self._paths.get(key, ()))) or "?"
            self.reporter.event(kind, paths=paths, **fields)

    def metrics(self) -> dict:
        """Cumulative protocol counters in the trainer/serve ``comm/*``
        key family."""
        return {"comm/slot_renegotiations": float(self.renegotiations),
                "comm/slot_resyncs": float(self.resyncs),
                "comm/slot_overflows": float(self.overflows)}
