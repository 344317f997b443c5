"""Wire-native codec fast paths: kernel-fused emission vs the copy path.

The transport moves ONE packed uint8 buffer per hop and produces/consumes
it through ``encode_wire`` / ``decode_wire`` / ``decode_sum_wire``.  The
generic implementations (``codecs.WireFastPath``) compose ``pack_wire`` /
``unpack_wire`` with encode/decode and DEFINE the byte format; TACO's
Pallas impls override them with fused kernels that write/read the packed
bytes at their static ``wire_layout(n)`` offsets directly.  The contract:

  1. ``encode_wire(x)`` is BIT-IDENTICAL to
     ``pack_wire(codec.encode(x), layout)`` for every registered codec —
     including the fused kernel impls (interpret mode on CPU);
  2. ``decode_wire`` / ``decode_sum_wire`` round-trip likewise against
     ``decode`` / ``decode_sum`` over ``unpack_wire``;
  3. the lowered HLO of a fused-path compressed AG/RS contains NO
     standalone concatenate between the encode and the collective (the
     copy path shows exactly the pack_wire concat).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.core import collectives as cc
from repro.core.codecs import pack_wire, unpack_wire
from repro.core.registry import codec_from_spec

# every registered compressing codec (generic wire path) plus the TACO
# variants that dispatch to the fused Pallas wire kernels (interpret mode),
# covering dual/folded metadata, quant groups, and the int8 payload dtype
WIRE_SPECS = [
    "taco:jnp", "taco:jnp:folded", "taco:jnp:g64",
    "taco:pallas_interpret", "taco:pallas_interpret:folded",
    "taco:pallas_interpret:g64", "taco:pallas_interpret:int8",
    "taco:pallas_interpret:e5m2:b128",
    "sdp4bit", "sdp4bit:b256", "tahquant", "int8", "int8:g64",
]

FUSED = codec_from_spec("taco:pallas_interpret")
COPY = codec_from_spec("taco:jnp")
ID = codec_from_spec("none")


def slot_input(rng, codec, slots=3, blocks=4):
    n = blocks * codec.granule
    return jnp.asarray(
        rng.normal(0, 0.02, (slots, n)).astype(np.float32)), n


# --------------------------------------------------------------------------
# 1+2: bit-identity of the fast paths vs the pack/unpack composition
# --------------------------------------------------------------------------

@pytest.mark.parametrize("spec", WIRE_SPECS)
def test_encode_wire_bit_identical_to_pack_wire(spec, rng):
    codec = codec_from_spec(spec)
    x, n = slot_input(rng, codec)
    layout = codec.wire_layout(n)
    want = pack_wire(codec.encode(x), layout)
    got = codec.encode_wire(x)
    assert got.dtype == jnp.uint8
    assert got.shape == (x.shape[0], layout.total_bytes)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("spec", WIRE_SPECS)
def test_decode_wire_bit_identical_to_unpack_decode(spec, rng):
    codec = codec_from_spec(spec)
    x, n = slot_input(rng, codec)
    layout = codec.wire_layout(n)
    wire = codec.encode_wire(x)
    want = codec.decode(unpack_wire(wire, layout), n, jnp.float32)
    got = codec.decode_wire(wire, n, jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("spec", WIRE_SPECS)
def test_decode_sum_wire_bit_identical_to_unpack_decode_sum(spec, rng):
    codec = codec_from_spec(spec)
    x, n = slot_input(rng, codec, slots=1)
    peers = jnp.concatenate(
        [codec.encode_wire(x), codec.encode_wire(-2.0 * x),
         codec.encode_wire(0.5 * x)])                        # (3, bytes)
    layout = codec.wire_layout(n)
    want = codec.decode_sum(unpack_wire(peers, layout), n, jnp.float32)
    got = codec.decode_sum_wire(peers, n, jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_wire_width_matches_layout_contract(rng):
    """kernels.ash_compress.wire_geometry must mirror taco.wire_components
    (the fused kernels compute offsets independently of the layout)."""
    from repro.kernels.ash_compress import wire_geometry
    for spec in ["taco:pallas_interpret", "taco:pallas_interpret:folded",
                 "taco:pallas_interpret:g32",
                 "taco:pallas_interpret:int8:b128"]:
        codec = codec_from_spec(spec)
        for blocks in (1, 3, 8):
            n = blocks * codec.granule
            *_, total = wire_geometry(codec.cfg, n)
            assert total == codec.wire_layout(n).total_bytes, spec


def test_on_device_fused_path_has_a_vmem_slot_budget():
    """impl=pallas (real TPU) takes the block kernels + pack_wire at every
    slot size: Mosaic refuses the fused wire kernels, so their on-device
    VMEM budget is zero.  Interpret mode stays fused at any size so the
    CPU parity/bench coverage is unbounded; jnp never fuses."""
    from repro.kernels import ops as kops
    assert kops.wire_kernel_impl(codec_from_spec("taco:pallas").cfg) is None
    assert kops.wire_kernel_impl(
        codec_from_spec("taco:pallas:folded").cfg) is None
    assert kops.wire_kernel_impl(FUSED.cfg) == "pallas_interpret"
    assert kops.wire_kernel_impl(codec_from_spec("taco:jnp").cfg) is None
    # the interpret-mode reduce kernel holds the whole (P, total) peer
    # stack, at any P: three peers decode-sum through it to 3x one peer
    x = jnp.ones((1, 512), jnp.float32)
    one = FUSED.decode_sum_wire(FUSED.encode_wire(x), 512, jnp.float32)
    stack = jnp.concatenate([FUSED.encode_wire(x)] * 3)   # (3, total)
    three = FUSED.decode_sum_wire(stack, 512, jnp.float32)
    np.testing.assert_allclose(np.asarray(three), 3 * np.asarray(one),
                               rtol=1e-6)


def test_identity_codec_has_no_wire_form():
    with pytest.raises(TypeError):
        ID.encode_wire(jnp.zeros((1, 8)))
    with pytest.raises(TypeError):
        ID.decode_wire(jnp.zeros((1, 8), jnp.uint8), 8, jnp.float32)


# --------------------------------------------------------------------------
# 3: fused-path HLO has no concatenate between encode and the collective
# --------------------------------------------------------------------------

def lowered_text(fn, x):
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                             check_vma=False)).lower(x).as_text()


def concat_count(txt):
    return len(re.findall(r"stablehlo\.concatenate", txt))


@pytest.mark.parametrize("make", [
    lambda c: (lambda v: cc.all_gather_c(v, "model", 0, c, ID)),
    lambda c: (lambda v: cc.psum_scatter_c(v, "model", 0, c, ID)),
], ids=["all_gather", "reduce_scatter"])
def test_fused_path_hlo_is_concat_free(make, rng):
    x = jnp.asarray(rng.normal(0, 0.02, (8, 512)).astype(np.float32))
    fused = concat_count(lowered_text(make(FUSED), x))
    copy = concat_count(lowered_text(make(COPY), x))
    # the whole fused module is concat-free: the kernel stores payload /
    # scale / alpha straight into the packed buffer; the copy path shows
    # exactly the pack_wire concatenate it exists to eliminate
    assert fused == 0, f"fused path lowered {fused} concatenates"
    assert copy >= 1, "copy path lost its pack_wire concat (update test?)"


def test_fused_transport_bit_identical_to_copy_transport(rng):
    """End-to-end through the real collectives: the fused kernels and the
    jnp copy path produce the same bytes, so AG/RS results are identical
    bit-for-bit (1-device mesh; the 8-device matrix runs in
    tests/multidev/check_parity.py)."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))

    def run(fn, x):
        return jax.jit(shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                                 check_vma=False))(x)

    x = jnp.asarray(rng.normal(0, 0.02, (8, 500)).astype(np.float32))
    for make in [lambda c: (lambda v: cc.all_gather_c(v, "model", 0, c, ID)),
                 lambda c: (lambda v: cc.psum_scatter_c(v, "model", 0, c,
                                                        ID))]:
        np.testing.assert_array_equal(
            np.asarray(run(make(FUSED), x)), np.asarray(run(make(COPY), x)))
