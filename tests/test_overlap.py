"""Wire-packing + chunked-ring-overlap transport tests.

Fast in-process coverage of the single-buffer wire engine (layout
invariants, pack/unpack bitcast round-trips, one-collective HLO on the
paths that lower on a 1-device mesh, ``chunks=N`` spec grammar, and
single-device parity); the full 8-device bit-identity + HLO-count matrix
runs in a subprocess (tests/multidev/check_parity.py), which scripts/ci.sh
also executes in its fail-fast gate.
"""
import os
import re
import subprocess
import sys
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.core import collectives as cc
from repro.core import overlap
from repro.core.codecs import IdentityCodec, TacoCodec
from repro.core.registry import (CommSpecError, codec_from_spec, from_spec,
                                 to_spec)
from repro.core.taco import TacoConfig

REPO = Path(__file__).resolve().parents[1]
ID = IdentityCodec()
TACO = TacoCodec(TacoConfig(impl="jnp"))

_COLLECTIVE = re.compile(
    r"stablehlo\.(all_gather|all_to_all|all_reduce|reduce_scatter"
    r"|collective_permute|collective_broadcast)\b")

# every registered compressing codec, plus arg'd variants with distinct
# component shapes (dual vs folded metadata, quant groups), plus hybrid
# lossless stacks (variable wire layouts: length header + zero-group
# compaction — repro.core.lossless)
LAYOUT_SPECS = ["taco:jnp", "taco:jnp:folded", "taco:jnp:g64",
                "sdp4bit", "sdp4bit:b256", "tahquant", "int8", "int8:g64",
                "taco+zle:jnp", "taco+zle:jnp:folded", "sdp4bit+zle",
                "int8+zle:g64"]


def one_dev_mesh():
    return jax.make_mesh((1, 1), ("data", "model"))


def lowered_collectives(fn, x):
    mesh = one_dev_mesh()
    txt = jax.jit(shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                            check_vma=False)).lower(x).as_text()
    return Counter(m.group(1) for m in _COLLECTIVE.finditer(txt))


def run1(fn, x):
    mesh = one_dev_mesh()
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=P(),
                             out_specs=P(), check_vma=False))(x)


# --------------------------------------------------------------------------
# wire layout invariants + pack/unpack round-trip
# --------------------------------------------------------------------------

@pytest.mark.parametrize("spec", LAYOUT_SPECS)
def test_wire_layout_matches_encode(spec, rng):
    codec = codec_from_spec(spec)
    n = 4 * codec.granule
    layout = codec.wire_layout(n)
    enc = codec.encode(jnp.asarray(
        rng.normal(0, 0.02, (3, n)).astype(np.float32)))
    assert len(layout.components) == len(enc)
    off = 0
    for comp, arr in zip(layout.components, enc):
        assert comp.offset == off, "components must be densely packed"
        assert comp.dtype == np.dtype(arr.dtype).name
        assert comp.size == arr.shape[-1]
        off += comp.nbytes
    assert layout.total_bytes == off


@pytest.mark.parametrize("spec", LAYOUT_SPECS)
def test_pack_unpack_roundtrip_bitexact(spec, rng):
    codec = codec_from_spec(spec)
    n = 4 * codec.granule
    layout = codec.wire_layout(n)
    enc = codec.encode(jnp.asarray(
        rng.normal(0, 0.02, (3, n)).astype(np.float32)))
    wire = cc.pack_wire(enc, layout)
    assert wire.dtype == jnp.uint8
    assert wire.shape == (3, layout.total_bytes)
    back = cc.unpack_wire(wire, layout)
    for a, b in zip(enc, back):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # unpack must also handle extra leading (peer) axes
    stacked = jnp.stack([wire, wire])
    back2 = cc.unpack_wire(stacked, layout)
    for a, b in zip(enc, back2):
        assert b.shape == (2,) + a.shape


def test_identity_codec_has_no_layout():
    assert ID.wire_layout(128) is None


# --------------------------------------------------------------------------
# HLO: one collective per packed compressed hop (1-device mesh lowers
# all_gather and collective_permute; the all_to_all paths are covered on
# the 8-device mesh in check_parity.py)
# --------------------------------------------------------------------------

def test_hlo_packed_all_gather_is_one_collective(rng):
    x = jnp.asarray(rng.normal(0, 0.02, (8, 512)).astype(np.float32))
    got = lowered_collectives(
        lambda v: cc.all_gather_c(v, "model", 0, TACO, ID), x)
    assert dict(got) == {"all_gather": 1}, got


def test_hlo_multibuffer_all_gather_one_collective_per_component(rng):
    x = jnp.asarray(rng.normal(0, 0.02, (8, 512)).astype(np.float32))
    with cc.multibuffer_wire():
        got = lowered_collectives(
            lambda v: cc.all_gather_c(v, "model", 0, TACO, ID), x)
    assert dict(got) == {"all_gather": 3}, got  # payload + scale + alpha


def test_hlo_packed_ppermute_is_one_collective(rng):
    x = jnp.asarray(rng.normal(0, 0.02, (8, 512)).astype(np.float32))
    got = lowered_collectives(
        lambda v: cc.ppermute_c(v, "model", ((0, 0),), TACO, ID), x)
    assert dict(got) == {"collective_permute": 1}, got


# --------------------------------------------------------------------------
# chunks=N spec grammar
# --------------------------------------------------------------------------

@pytest.mark.parametrize("spec", [
    "tp=taco:folded:chunks=4",
    "tp=taco:b128:jnp:chunks=2",
    "grad_rs=sdp4bit:chunks=2",
    "pp=tahquant:chunks=8",
    "weight_ag=int8:g64:chunks=2",
])
def test_chunks_spec_roundtrip(spec):
    plan = from_spec(spec)
    assert to_spec(plan) == spec
    assert from_spec(to_spec(plan)) == plan


def test_chunks_one_is_the_default_and_not_emitted():
    assert to_spec(from_spec("tp=taco:chunks=1")) == "tp=taco"
    assert from_spec("tp=taco:chunks=1") == from_spec("tp=taco")


@pytest.mark.parametrize("bad", [
    "tp=taco:chunks=0",
    "tp=taco:chunks=-2",
    "tp=taco:chunks=x",
    "tp=taco:chunks=",
    "tp=taco:chunks=4:chunks=2",
    "tp=none:chunks=4",          # no wire layout -> rejected
    "pp=none:chunks=2",
])
def test_bad_chunks_specs_rejected(bad):
    with pytest.raises(CommSpecError):
        from_spec(bad)


@pytest.mark.parametrize("spec", [
    "tp=taco:chunks=4:schedule=serial",
    "tp=taco:schedule=serial",                  # no-op at chunks=1, kept
    "grad_rs=sdp4bit:chunks=2:schedule=serial",
    "pp=tahquant:schedule=serial",
    "weight_ag=int8:g64:chunks=2:schedule=serial",
])
def test_schedule_spec_roundtrip(spec):
    plan = from_spec(spec)
    assert to_spec(plan) == spec
    assert from_spec(to_spec(plan)) == plan


def test_schedule_pipelined_is_the_default_and_not_emitted():
    assert to_spec(from_spec("tp=taco:chunks=4:schedule=pipelined")) == \
        "tp=taco:chunks=4"
    assert from_spec("tp=taco:chunks=4:schedule=pipelined") == \
        from_spec("tp=taco:chunks=4")


@pytest.mark.parametrize("bad", [
    "tp=taco:schedule=async",
    "tp=taco:schedule=",
    "tp=taco:schedule=Serial",
    "tp=none:schedule=serial",           # identity takes no args
    "grad_rs=sdp4bit:schedule=eager",
    "pp=tahquant:schedule=2",
])
def test_bad_schedule_specs_rejected(bad):
    with pytest.raises(CommSpecError):
        from_spec(bad)


def test_chunks_threads_through_plan_telemetry():
    plan = from_spec("tp=taco:chunks=4,grad_rs=sdp4bit:chunks=2")
    assert plan.wire_chunks() == {"tp_fwd": 4, "tp_bwd": 4, "grad_rs": 2,
                                  "weight_ag": 1, "pp": 1, "sp": 1}
    assert from_spec("baseline").wire_chunks() == \
        {p: 1 for p in plan.wire_chunks()}


# --------------------------------------------------------------------------
# codec-stack (+zle) spec grammar
# --------------------------------------------------------------------------

@pytest.mark.parametrize("spec", [
    "tp=taco+zle",
    "tp=taco+zle:folded:chunks=4",
    "tp=taco+zle:b128:jnp:chunks=2:schedule=serial",
    "grad_rs=sdp4bit+zle:chunks=2",
    "weight_ag=int8+zle:g64",
    "pp=tahquant+zle",
])
def test_stack_spec_roundtrip(spec):
    plan = from_spec(spec)
    assert to_spec(plan) == spec
    assert from_spec(to_spec(plan)) == plan


def test_stack_codec_spec_roundtrip():
    from repro.core.registry import codec_to_spec
    c = codec_from_spec("taco+zle:folded:chunks=4")
    assert codec_to_spec(c) == "taco+zle:folded:chunks=4"
    assert codec_from_spec(codec_to_spec(c)) == c


def test_stack_transport_knobs_delegate_to_base():
    c = codec_from_spec("taco+zle:folded:chunks=4:schedule=serial")
    assert c.chunks == 4 and c.schedule == "serial"
    assert c.granule == c.inner.granule == 256


@pytest.mark.parametrize("bad", [
    "tp=none+zle",               # no wire layout to stack over
    "tp=taco+bogus",             # unregistered stage
    "tp=+zle",                   # empty base
    "tp=zle",                    # a stage is not a codec head
    "grad_rs=none+zle:chunks=2",
])
def test_bad_stack_specs_rejected(bad):
    with pytest.raises(CommSpecError):
        from_spec(bad)


# --------------------------------------------------------------------------
# multibuffer_wire is a contextvar: nesting restores the enclosing state
# --------------------------------------------------------------------------

def test_multibuffer_wire_nesting_restores_enclosing_state():
    """Regression for the module-global toggle: nested contexts must
    restore the EXACT enclosing value on exit (token-based contextvar
    reset), so a nested parity helper cannot flip an outer test back to
    packed mode early — and the default survives an exception."""
    assert cc._WIRE_PACKING.get() is True
    with cc.multibuffer_wire():
        assert cc._WIRE_PACKING.get() is False
        with cc.multibuffer_wire():
            assert cc._WIRE_PACKING.get() is False
        # inner exit must NOT restore packed mode — outer is still open
        assert cc._WIRE_PACKING.get() is False
    assert cc._WIRE_PACKING.get() is True
    with pytest.raises(RuntimeError):
        with cc.multibuffer_wire():
            raise RuntimeError("boom")
    assert cc._WIRE_PACKING.get() is True


def test_multibuffer_wire_isolated_per_context():
    """Concurrent contexts each see their own toggle value (the leak the
    module global allowed)."""
    import contextvars

    def probe_inside():
        with cc.multibuffer_wire():
            return cc._WIRE_PACKING.get()

    ctx = contextvars.copy_context()
    assert ctx.run(probe_inside) is False
    # the other context's window never touched THIS context's value
    assert cc._WIRE_PACKING.get() is True


# --------------------------------------------------------------------------
# single-device parity (degenerate P=1 ring; full matrix is multi-device)
# --------------------------------------------------------------------------

def _three_path_parity(x, chunks=4, base="taco:jnp"):
    """Monolithic packed, chunked ring (BOTH stage schedules), and
    multi-buffer transports must agree bit-for-bit on ``x`` for both AG
    and RS.  ``base`` is the codec spec HEAD (args included) the ring
    variants are derived from by appending transport args — works for
    plain codecs and for hybrid ``+zle`` stacks alike."""
    mono = codec_from_spec(base)
    ring = codec_from_spec(f"{base}:chunks={chunks}")
    serial = codec_from_spec(f"{base}:chunks={chunks}:schedule=serial")
    for make in [lambda c: (lambda v: cc.all_gather_c(v, "model", 0, c, ID)),
                 lambda c: (lambda v: cc.psum_scatter_c(v, "model", 0, c, ID))]:
        packed = run1(make(mono), x)
        with cc.multibuffer_wire():
            multi = run1(make(mono), x)
        chunked = run1(make(ring), x)
        chunked_serial = run1(make(serial), x)
        np.testing.assert_array_equal(np.asarray(packed), np.asarray(multi))
        np.testing.assert_array_equal(np.asarray(packed), np.asarray(chunked))
        np.testing.assert_array_equal(np.asarray(packed),
                                      np.asarray(chunked_serial))


def test_single_device_packed_and_ring_parity(rng):
    _three_path_parity(jnp.asarray(
        rng.normal(0, 0.02, (8, 500)).astype(np.float32)))


def test_single_device_hybrid_zle_parity(rng):
    """The hybrid taco+zle stack holds the same four-way transport parity
    as its base codec, AND decodes bit-identically to BARE taco (the
    lossless stage is exact)."""
    x = jnp.asarray(rng.normal(0, 0.02, (8, 500)).astype(np.float32))
    _three_path_parity(x, base="taco+zle:jnp")
    hybrid = codec_from_spec("taco+zle:jnp")
    for make in [lambda c: (lambda v: cc.all_gather_c(v, "model", 0, c, ID)),
                 lambda c: (lambda v: cc.psum_scatter_c(v, "model", 0, c,
                                                        ID))]:
        np.testing.assert_array_equal(np.asarray(run1(make(TACO), x)),
                                      np.asarray(run1(make(hybrid), x)))


# --------------------------------------------------------------------------
# the software-pipelined ring scheduler (repro.core.overlap)
# --------------------------------------------------------------------------

def _logged_stages(log):
    """Stub encode/transfer/decode that record (stage, chunk) call order.

    encode maps chunk value c -> 10c, transfer -> 10c+1, so each stage
    can recover which chunk it was handed even after the buffers cross
    the scheduler's optimization-barrier fences."""
    def enc(s):
        log.append(("E", int(s)))
        return s * 10
    def tx(w):
        log.append(("T", int(w) // 10))
        return w + 1
    def dec(a):
        log.append(("D", (int(a) - 1) // 10))
        return a
    return enc, tx, dec


def test_run_ring_pipelined_emits_the_stage_tick_schedule():
    """Pipelined emission order is exactly the double-buffered
    (encode[t], transfer[t-1], decode[t-2]) tick schedule with prologue
    and epilogue, and outputs come back in chunk (FIFO) order."""
    log = []
    enc, tx, dec = _logged_stages(log)
    segs = [jnp.float32(c) for c in range(4)]
    outs = overlap.run_ring(segs, encode=enc, transfer=tx, decode=dec,
                            schedule=overlap.PIPELINED)
    assert [int(o) for o in outs] == [1, 11, 21, 31]
    assert log == [
        ("E", 0),                        # tick 0: prologue
        ("E", 1), ("T", 0),              # tick 1: prologue
        ("E", 2), ("T", 1), ("D", 0),    # tick 2: steady state
        ("E", 3), ("T", 2), ("D", 1),    # tick 3: steady state
        ("T", 3), ("D", 2),              # tick 4: epilogue
        ("D", 3),                        # tick 5: epilogue
    ]


def test_run_ring_serial_hoists_stages():
    """Serial emission is the hoisted baseline: all encodes, then all
    transfers, then all decodes."""
    log = []
    enc, tx, dec = _logged_stages(log)
    segs = [jnp.float32(c) for c in range(3)]
    outs = overlap.run_ring(segs, encode=enc, transfer=tx, decode=dec,
                            schedule=overlap.SERIAL)
    assert [int(o) for o in outs] == [1, 11, 21]
    assert log == [("E", 0), ("E", 1), ("E", 2),
                   ("T", 0), ("T", 1), ("T", 2),
                   ("D", 0), ("D", 1), ("D", 2)]


def test_run_ring_single_chunk_degenerates_to_serial():
    """One chunk has nothing to pipeline with — no fence noise."""
    log = []
    enc, tx, dec = _logged_stages(log)
    outs = overlap.run_ring([jnp.float32(0)], encode=enc, transfer=tx,
                            decode=dec, schedule=overlap.PIPELINED)
    assert [int(o) for o in outs] == [1]
    assert log == [("E", 0), ("T", 0), ("D", 0)]


def test_run_ring_empty_and_bad_schedule():
    assert overlap.run_ring([], encode=None, transfer=None, decode=None) == []
    with pytest.raises(ValueError, match="unknown ring schedule"):
        overlap.run_ring([jnp.float32(0)], encode=None, transfer=None,
                         decode=None, schedule="eager")


def test_ring_schedule_reads_the_codec_knob():
    import dataclasses
    assert overlap.ring_schedule(TACO) == overlap.PIPELINED
    assert overlap.ring_schedule(
        dataclasses.replace(TACO, schedule="serial")) == overlap.SERIAL
    assert overlap.ring_schedule(ID) == overlap.PIPELINED  # no knob: default
    with pytest.raises(ValueError, match="unknown ring schedule"):
        overlap.ring_schedule(dataclasses.replace(TACO, schedule="bogus"))


def test_hlo_pipelined_ring_fences_serial_ring_does_not(rng):
    """The pipelined schedule emits one optimization_barrier per tick
    (chunks + 2 of them); the serial schedule emits none.  (The encode/
    ppermute interleave itself needs P > 1 and is asserted on the
    8-device mesh in tests/multidev/check_parity.py.)"""
    x = jnp.asarray(rng.normal(0, 0.02, (8, 512)).astype(np.float32))
    mesh = one_dev_mesh()

    def lowered(codec):
        return jax.jit(shard_map(
            lambda v: cc.all_gather_c(v, "model", 0, codec, ID),
            mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False)).lower(x).as_text()

    chunks = 4
    pipe = codec_from_spec(f"taco:jnp:chunks={chunks}")
    ser = codec_from_spec(f"taco:jnp:chunks={chunks}:schedule=serial")
    assert lowered(pipe).count("stablehlo.optimization_barrier") == chunks + 2
    assert lowered(ser).count("stablehlo.optimization_barrier") == 0


# --------------------------------------------------------------------------
# degenerate transport shapes: all three paths bit-identical
# --------------------------------------------------------------------------

def test_degenerate_trailing_dim_smaller_than_granule(rng):
    # 8*100 = 800 elements/slot < granule 256 on the AG path slot? no —
    # the AG slot is the whole flattened tensor; make the per-slot
    # trailing dim itself sub-granule: (1, 100) -> one 100-element slot
    _three_path_parity(jnp.asarray(
        rng.normal(0, 0.02, (1, 100)).astype(np.float32)))


def test_degenerate_exact_chunks_granule_multiple(rng):
    # trailing dim an exact multiple of chunks*granule: NO padding on
    # either the monolithic (pad to granule) or ring (pad to
    # chunks*granule) layout
    _three_path_parity(jnp.asarray(
        rng.normal(0, 0.02, (4, 1024)).astype(np.float32)), chunks=4)


def test_degenerate_chunks_exceed_block_count(rng):
    # 100 elements = ONE 256-block after granule padding, but chunks=8
    # rings 8 wire slices — the transport must pad to chunks*granule
    # (2048) and stay bit-identical, not crash or truncate
    _three_path_parity(jnp.asarray(
        rng.normal(0, 0.02, (1, 100)).astype(np.float32)), chunks=8)


def test_chunks_exceed_block_count_multiblock_one_ulp(rng):
    """chunks=8 over a 2-3 block tensor: ring chunks decode ONE block per
    call where the monolithic path decodes all blocks in one batch, and
    XLA:CPU dispatches m=1 dots (gemv) with a different accumulation
    schedule than m>1 (gemm) — a backend instruction-selection artifact,
    not transport corruption.  The wire BYTES are bit-identical (asserted
    below); the decoded floats may differ by 1 ulp of the inverse
    rotation.  When decode batch structures match (the other degenerate
    tests, and every multi-device shape in check_parity.py) results are
    bit-identical."""
    x = jnp.asarray(rng.normal(0, 0.02, (2, 300)).astype(np.float32))
    ring = codec_from_spec("taco:jnp:chunks=8")
    # wire bytes: monolithic slot vs concatenated ring slices, bit-equal
    flat = x.reshape(1, -1)
    segs, _, csz = cc._chunk_slices(flat, ring)
    ring_wire = jnp.concatenate([ring.encode_wire(s)[:, :csz]
                                 for s in segs], axis=-1)
    mono_padded, _ = cc._pad_to(flat, TACO.granule)
    mono_wire = TACO.encode_wire(mono_padded)
    np.testing.assert_array_equal(
        np.asarray(mono_wire[:, :mono_padded.shape[-1]]),
        np.asarray(ring_wire[:, :mono_padded.shape[-1]]))
    # decoded values: identical to 1 ulp
    for make in [lambda c: (lambda v: cc.all_gather_c(v, "model", 0, c, ID)),
                 lambda c: (lambda v: cc.psum_scatter_c(v, "model", 0, c,
                                                        ID))]:
        np.testing.assert_allclose(
            np.asarray(run1(make(TACO), x)),
            np.asarray(run1(make(ring), x)), rtol=0, atol=1e-7)


# --------------------------------------------------------------------------
# shape validation: ValueError (not a -O-strippable assert) with context
# --------------------------------------------------------------------------

@pytest.mark.parametrize("chunks", [1, 4])
def test_rs_indivisible_scatter_dim_raises_fake_axis(chunks, monkeypatch,
                                                     rng):
    """_rs_one/_rs_one_ring divisibility: patch axis_size so the check
    trips without a multi-device mesh, and assert the message carries the
    dim/axis context."""
    monkeypatch.setattr(cc, "axis_size", lambda ax: 4)
    codec = codec_from_spec(f"taco:jnp:chunks={chunks}")
    x = jnp.zeros((6, 8), jnp.float32)  # 6 % 4 != 0
    with pytest.raises(ValueError, match=r"scatter dim 0 has size 6.*model"):
        cc._rs_impl(x, "model", 0, codec)


def test_a2a_indivisible_split_dim_raises_fake_axis(monkeypatch):
    monkeypatch.setattr(cc, "axis_size", lambda ax: 4)
    codec = codec_from_spec("taco:jnp")
    x = jnp.zeros((6, 8), jnp.float32)
    with pytest.raises(ValueError, match=r"split dim 0 has size 6.*model"):
        cc._a2a_impl(x, "model", 0, 0, codec)


# --------------------------------------------------------------------------
# wire-byte telemetry == actual packed buffer size (incl. chunk padding)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("spec,n", [
    ("taco:jnp", 500),                    # ragged: pads 500 -> 512
    ("taco:jnp:chunks=4", 500),           # ragged+ring: pads 500 -> 1024
    ("taco:jnp:folded:chunks=4", 1000),   # pads 1000 -> 1024
    ("sdp4bit:chunks=2", 100),            # pads 100 -> 256
    ("tahquant", 64),                     # exact: no padding
    ("int8:g64:chunks=2", 96),            # pads 96 -> 128
])
def test_wire_slot_bytes_equals_packed_buffer(spec, n, rng):
    codec = codec_from_spec(spec)
    told = cc.wire_slot_bytes(codec, n)
    # actually pad + slice + encode exactly as the transport does
    chunks = int(getattr(codec, "chunks", 1))
    x = jnp.asarray(rng.normal(0, 0.02, (1, n)).astype(np.float32))
    segs, n0, csz = cc._chunk_slices(x, codec)
    actual = sum(int(codec.encode_wire(seg).shape[-1]) for seg in segs)
    assert told == actual, (spec, n, told, actual)
    assert len(segs) == chunks and n0 == n


def test_gather_scatter_wire_bytes_ragged(rng):
    """gather/scatter telemetry counts the padded packed buffer, not the
    pre-padding element count."""
    ring = codec_from_spec("taco:jnp:chunks=4")
    n = 500   # pads to 1024 under chunks*granule
    per_slot = cc.wire_slot_bytes(ring, n)
    assert cc.gather_wire_bytes((n,), jnp.float32, 8, ring) == \
        per_slot * 7
    assert cc.scatter_wire_bytes((8 * n,), jnp.float32, 8, ring) == \
        per_slot * 7
    # the old element-count formula under-reports on ragged sizes
    assert per_slot > n * ring.bytes_per_element()
    # identity: raw dtype bytes, unchanged semantics
    assert cc.gather_wire_bytes((n,), jnp.float32, 8, ID) == n * 4 * 7


def test_commplan_wire_bytes_per_element_exact_with_n():
    from repro.core.registry import from_spec
    plan = from_spec("tp=taco:chunks=4")
    n = 500
    exact = plan.wire_bytes_per_element(n)
    asym = plan.wire_bytes_per_element()
    assert exact["tp_fwd"] == cc.wire_slot_bytes(plan.tp_fwd, n) / n
    assert exact["tp_fwd"] > asym["tp_fwd"]        # padding surfaced
    assert exact["grad_rs"] == asym["grad_rs"]     # identity path unchanged


def test_pp_path_telemetry_never_chunk_pads(rng):
    """ppermute hops route chunked codecs through the monolithic
    transport (granule-only padding), so pp telemetry must not count the
    chunks*granule padding the ring AG/RS paths would."""
    from repro.core.registry import from_spec
    plan = from_spec("pp=tahquant:chunks=2")
    n = 100   # granule 64: pads to 128 monolithic, 128 ring — use taco
    plan4 = from_spec("pp=taco:chunks=4")
    got = plan4.wire_bytes_per_element(n)["pp"]
    # actual ppermute wire buffer: monolithic pad to ONE granule
    padded, _ = cc._pad_to(jnp.zeros((1, n), jnp.float32), plan4.pp.granule)
    actual = plan4.pp.encode_wire(padded).shape[-1]
    assert got == actual / n
    assert got < cc.wire_slot_bytes(plan4.pp, n) / n   # ring padding bigger
    assert plan.wire_bytes_per_element(64)["pp"] == \
        cc.wire_slot_bytes(plan.pp, 64, chunks=1) / 64


# --------------------------------------------------------------------------
# all-to-all: degenerate/ragged shapes + telemetry (the monolithic-only
# transport — chunks= must be ignored, not break it)
# --------------------------------------------------------------------------

def _a2a1(codec, x):
    return run1(lambda v: cc.all_to_all_c(v, "model", 0, 0, codec, ID), x)


def test_a2a_sub_granule_slot_all_transports_agree(rng):
    """Per-peer slot smaller than the codec granule: packed, multibuffer,
    and chunked-codec (chunks ignored) a2a all agree bit-for-bit."""
    x = jnp.asarray(rng.normal(0, 0.02, (1, 100)).astype(np.float32))
    ring = codec_from_spec("taco:jnp:chunks=8")   # chunks > blocks too
    packed = _a2a1(TACO, x)
    with cc.multibuffer_wire():
        multi = _a2a1(TACO, x)
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(multi))
    np.testing.assert_array_equal(np.asarray(packed),
                                  np.asarray(_a2a1(ring, x)))


def test_a2a_chunked_codec_never_rings(rng):
    """chunks=N never rings the a2a hop: no collective_permute in the
    lowering (a 1-device all_to_all itself optimizes away; the exact
    one-collective count is asserted on the 8-device mesh in
    check_parity.py)."""
    x = jnp.asarray(rng.normal(0, 0.02, (8, 512)).astype(np.float32))
    ring = codec_from_spec("taco:jnp:chunks=4")
    got = lowered_collectives(
        lambda v: cc.all_to_all_c(v, "model", 0, 0, ring, ID), x)
    assert "collective_permute" not in got, got


def test_a2a_hybrid_zle_parity_and_vs_bare(rng):
    x = jnp.asarray(rng.normal(0, 0.02, (4, 250)).astype(np.float32))
    hybrid = codec_from_spec("taco+zle:jnp")
    packed = _a2a1(hybrid, x)
    with cc.multibuffer_wire():
        multi = _a2a1(hybrid, x)
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(multi))
    np.testing.assert_array_equal(np.asarray(packed),
                                  np.asarray(_a2a1(TACO, x)))


def test_a2a_wire_bytes_telemetry(rng):
    """a2a telemetry: per-peer slots, chunks ignored (chunks=1 slot
    size), achieved sample path <= the static bound."""
    p, n = 8, 500 * 8
    ring = codec_from_spec("taco:jnp:chunks=4")
    # chunked codec: a2a slots are chunks=1 (monolithic), NOT ring-padded
    assert cc.a2a_wire_bytes((n,), jnp.float32, p, ring) == \
        cc.wire_slot_bytes(ring, n // p, chunks=1) * (p - 1)
    assert cc.a2a_wire_bytes((n,), jnp.float32, p, ID) == \
        (n // p) * 4 * (p - 1)
    hybrid = codec_from_spec("taco+zle:jnp")
    bound = cc.a2a_wire_bytes((n,), jnp.float32, p, hybrid)
    zeros = jnp.zeros((n,), jnp.float32)
    achieved = cc.a2a_wire_bytes((n,), jnp.float32, p, hybrid, sample=zeros)
    assert achieved < bound
    # static layout: sample path must equal the bound exactly
    taco = codec_from_spec("taco:jnp")
    assert cc.a2a_wire_bytes((n,), jnp.float32, p, taco, sample=zeros) == \
        cc.a2a_wire_bytes((n,), jnp.float32, p, taco)


# --------------------------------------------------------------------------
# achieved (data-dependent) byte telemetry for variable wire layouts
# --------------------------------------------------------------------------

def test_achieved_slot_bytes_static_layout_equals_bound(rng):
    codec = codec_from_spec("taco:jnp:chunks=4")
    x = jnp.asarray(rng.normal(0, 0.02, (3, 500)).astype(np.float32))
    ach = cc.achieved_slot_bytes(codec, x)
    want = cc.wire_slot_bytes(codec, 500)
    np.testing.assert_array_equal(np.asarray(ach), [want] * 3)
    assert cc.achieved_slot_bytes(ID, x) is None


def test_achieved_slot_bytes_variable_layout_tracks_data(rng):
    """Hybrid zle: achieved bytes equal the summed length headers, stay
    <= the slot bound, and drop when the payload zeroes out."""
    codec = codec_from_spec("taco+zle:jnp:chunks=4")
    n = 2048
    dense = jnp.asarray(rng.normal(0, 0.02, (2, n)).astype(np.float32))
    sparse = dense.at[:, n // 4:].set(0.0)
    bound = cc.wire_slot_bytes(codec, n)
    a_dense = np.asarray(cc.achieved_slot_bytes(codec, dense))
    a_sparse = np.asarray(cc.achieved_slot_bytes(codec, sparse))
    assert (a_dense <= bound).all() and (a_sparse <= bound).all()
    assert (a_sparse < a_dense).all()
    # mirror the transport's chunk slicing by hand: headers must match
    segs, _, csz = cc._chunk_slices(sparse, codec)
    layout = codec.wire_layout(csz)
    assert layout.variable
    want = sum(np.asarray(cc.achieved_wire_bytes(codec.encode_wire(s),
                                                 layout)) for s in segs)
    np.testing.assert_array_equal(a_sparse, want)


def test_gather_scatter_wire_bytes_sample_path(rng):
    p, n = 8, 1024
    hybrid = codec_from_spec("taco+zle:jnp")
    zeros = jnp.zeros((n,), jnp.float32)
    dense = jnp.asarray(rng.normal(0, 0.02, (n,)).astype(np.float32))
    g_bound = cc.gather_wire_bytes((n,), jnp.float32, p, hybrid)
    assert cc.gather_wire_bytes((n,), jnp.float32, p, hybrid,
                                sample=zeros) < g_bound
    s_bound = cc.scatter_wire_bytes((p * n,), jnp.float32, p, hybrid)
    assert cc.scatter_wire_bytes((p * n,), jnp.float32, p, hybrid,
                                 sample=jnp.zeros((p * n,), jnp.float32)) \
        < s_bound
    # static layouts: sample changes nothing
    taco = codec_from_spec("taco:jnp")
    assert cc.gather_wire_bytes((n,), jnp.float32, p, taco, sample=dense) \
        == cc.gather_wire_bytes((n,), jnp.float32, p, taco)
    # identity: no layout, sample ignored, raw bytes
    assert cc.gather_wire_bytes((n,), jnp.float32, p, ID, sample=zeros) \
        == n * 4 * (p - 1)


def test_commplan_wire_variable_flags():
    plan = from_spec("tp=taco+zle,grad_rs=sdp4bit")
    assert plan.wire_variable() == {
        "tp_fwd": True, "tp_bwd": True, "grad_rs": False,
        "weight_ag": False, "pp": False, "sp": False}
    assert from_spec("baseline").wire_variable() == \
        {p: False for p in plan.wire_variable()}


def test_hlo_hybrid_zle_packed_one_collective_multibuf_three(rng):
    x = jnp.asarray(rng.normal(0, 0.02, (8, 512)).astype(np.float32))
    hybrid = codec_from_spec("taco+zle:jnp")
    got = lowered_collectives(
        lambda v: cc.all_gather_c(v, "model", 0, hybrid, ID), x)
    assert dict(got) == {"all_gather": 1}, got
    with cc.multibuffer_wire():
        got = lowered_collectives(
            lambda v: cc.all_gather_c(v, "model", 0, hybrid, ID), x)
    assert dict(got) == {"all_gather": 3}, got   # length + bitmap + data


# --------------------------------------------------------------------------
# the full 8-device matrix
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_multidevice_transport_parity_subprocess():
    """Bit-identity of packed/chunked vs monolithic multi-buffer for every
    codec + exact HLO collective counts, on a real (2, 4) device mesh."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(REPO / "src")
    proc = subprocess.run(
        [sys.executable, str(REPO / "tests" / "multidev" / "check_parity.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "ALL TRANSPORT PARITY CHECKS PASSED" in proc.stdout
