"""Transport-parity checks for the packed-wire + chunked-ring engine.

Run in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
(see tests/test_overlap.py). Exits nonzero on any failure.

Four contracts, for EVERY registered compressing codec (taco dual/folded,
sdp4bit, tahquant, int8) AND the hybrid lossless stacks (taco+zle —
bounded-but-ragged variable wire layouts, repro.core.lossless):

  1. packed single-buffer transport is BIT-IDENTICAL to the multi-buffer
     transport (the packing is pure bitcast/concat plumbing);
  2. chunked ring transport (chunks=N) is BIT-IDENTICAL to the monolithic
     single-collective transport (contributions are compressed once; peer
     sums run at the destination in peer-index order) — including ragged
     trailing sizes that force different internal padding, and under BOTH
     ring stage schedules (schedule=pipelined / schedule=serial);
  3. lowered HLO: every packed compressed hop issues exactly ONE lax
     collective (all-gather / all-to-all / collective-permute), the
     multi-buffer layout issues one per wire component, and the ring
     issues exactly chunks*(P-1) collective-permutes under either
     schedule;
  4. lowered HLO structure of the ring schedules: the pipelined schedule
     provably interleaves encode ops between the ppermute ring steps and
     fences its ticks with optimization_barriers, the serial schedule
     hoists every encode above the first ppermute with no fences, and the
     ring reduce-scatter's hoisted per-peer send gather leaves ZERO
     dynamic-slices of the wire matrix in the step loop;
  5. transposed (Ulysses, ``split_dim != concat_dim``) all-to-all: the
     identity codec is BIT-IDENTICAL to raw tiled ``lax.all_to_all`` in
     both directions (and round-trips to the input), every compressing
     codec reproduces the flat equal-dims transport of the moved layout
     bit-for-bit (packed and multibuffer), its gradient is the inverse
     redistribute with swapped codecs (the ``custom_vjp`` contract), the
     compressed hop lowers to exactly ONE all-to-all, and the negotiated
     (slot=auto) bound keeps the hop bit-identical while moving fewer
     bytes;
  6. negotiated (slot=auto) hops: a static BOOTSTRAP step (probes
     observing the true per-device chunk geometry) feeds the
     SlotController, whose negotiated moved bound then keeps the AG and
     RS transports BIT-IDENTICAL to their static-bound hops on the
     8-device mesh while moving strictly fewer bytes, with no overflow
     on the observed workload, and the lowered HLO still shows exactly
     ONE lax collective per packed hop (the ring its usual
     chunks*(P-1) permutes).
"""
import os
import re
from collections import Counter

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.core import collectives as cc
from repro.core.codecs import (IdentityCodec, Int8Codec, Sdp4BitCodec,
                               TacoCodec, TahQuantCodec)
from repro.core.lossless import ZleCodec
from repro.core.registry import codec_from_spec, codec_to_spec
from repro.core.taco import TacoConfig

ID = IdentityCodec()
CODECS = {
    "taco": TacoCodec(TacoConfig(impl="jnp")),
    "taco_folded": TacoCodec(TacoConfig(impl="jnp", metadata="folded")),
    # fused wire-emission kernels (interpret mode): encode_wire/decode_wire/
    # decode_sum_wire run in the Pallas kernels, multibuffer stays on the
    # component path — packed-vs-multibuf parity therefore also pins
    # kernel-vs-jnp wire bytes
    "taco_fused": TacoCodec(TacoConfig(impl="pallas_interpret")),
    "taco_fused_folded": TacoCodec(TacoConfig(impl="pallas_interpret",
                                              metadata="folded")),
    "sdp4bit": Sdp4BitCodec(),
    "tahquant": TahQuantCodec(),
    "int8": Int8Codec(),
    # hybrid lossless stacks: VARIABLE wire layouts (length header +
    # zero-group compaction over the inner packed buffer) riding the
    # same transports — all parity/HLO contracts must hold unchanged
    "taco_zle": ZleCodec(TacoCodec(TacoConfig(impl="jnp"))),
    "taco_zle_folded": ZleCodec(TacoCodec(TacoConfig(impl="jnp",
                                                     metadata="folded"))),
}
CHUNKS = 4
TP = 4  # model-axis size of the (2, 4) mesh


def with_ring(codec, schedule=None):
    """Derive the chunked-ring variant of ``codec`` through the spec
    grammar (``dataclasses.replace`` can't set ``chunks`` on the hybrid
    wrappers — their transport knobs are delegating properties)."""
    spec = codec_to_spec(codec) + f":chunks={CHUNKS}"
    if schedule is not None:
        spec += f":schedule={schedule}"
    return codec_from_spec(spec)

mesh = jax.make_mesh((2, 4), ("data", "model"))
rng = np.random.default_rng(3)
FAILURES = []

_COLLECTIVE = re.compile(
    r"stablehlo\.(all_gather|all_to_all|all_reduce|reduce_scatter"
    r"|collective_permute|collective_broadcast)\b")


def check_equal(name, got, want):
    same = np.array_equal(np.asarray(got), np.asarray(want))
    print(f"{'PASS' if same else 'FAIL'} {name}: bit-identical={same}")
    if not same:
        FAILURES.append(name)


def check_counts(name, counter, want):
    ok = dict(counter) == want
    print(f"{'PASS' if ok else 'FAIL'} {name}: collectives={dict(counter)} "
          f"want={want}")
    if not ok:
        FAILURES.append(name)


def check_true(name, ok, detail):
    print(f"{'PASS' if ok else 'FAIL'} {name}: {detail}")
    if not ok:
        FAILURES.append(name)


def jit_sm(fn, in_spec, out_spec):
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_spec,
                             out_specs=out_spec, check_vma=False))


def lowered_text(fn, x, in_spec, out_spec):
    return jit_sm(fn, in_spec, out_spec).lower(x).as_text()


def collectives_of(fn, x, in_spec, out_spec):
    txt = lowered_text(fn, x, in_spec, out_spec)
    return Counter(m.group(1) for m in _COLLECTIVE.finditer(txt))


def run(fn, x, in_spec, out_spec):
    return jit_sm(fn, in_spec, out_spec)(x)


# ---------------------------------------------------------------- parity
# ragged trailing size: 8*500 elements per device is NOT a multiple of any
# codec granule, exercising the different pad-to-granule vs
# pad-to-chunks*granule internal layouts
x_ag = jnp.asarray(rng.normal(0, 0.02, (16, 512)).astype(np.float32))
x_ragged = jnp.asarray(rng.normal(0, 0.02, (16, 500)).astype(np.float32))
x_rs = jnp.asarray(rng.normal(0, 0.02, (16, 512)).astype(np.float32))
x_a2a = jnp.asarray(rng.normal(0, 0.02, (32, 256)).astype(np.float32))
# ragged a2a: 8 rows/peer x 250 = 2000 elements/slot, no granule divides it
x_a2a_ragged = jnp.asarray(rng.normal(0, 0.02, (32, 250)).astype(np.float32))
PERM = tuple((i, (i + 1) % TP) for i in range(TP))


def _mb(fn, x, in_spec, out_spec):
    with cc.multibuffer_wire():
        return run(fn, x, in_spec, out_spec)

for name, codec in CODECS.items():
    ring = with_ring(codec)
    ring_serial = with_ring(codec, schedule="serial")

    def ag(v, c=codec):
        return cc.all_gather_c(v, "model", 0, c, ID)

    def ag_ring(v, c=ring):
        return cc.all_gather_c(v, "model", 0, c, ID)

    def ag_ring_serial(v, c=ring_serial):
        return cc.all_gather_c(v, "model", 0, c, ID)

    def rs(v, c=codec):
        return cc.psum_scatter_c(v, "model", 0, c, ID)

    def rs_ring(v, c=ring):
        return cc.psum_scatter_c(v, "model", 0, c, ID)

    def rs_ring_serial(v, c=ring_serial):
        return cc.psum_scatter_c(v, "model", 0, c, ID)

    def ar(v, c=codec):
        return cc.allreduce_g(v, "model", c, ID)

    def ar_ring(v, c=ring):
        return cc.allreduce_g(v, "model", c, ID)

    def pp(v, c=codec):
        return cc.ppermute_c(v, "model", PERM, c, ID)

    def a2a(v, c=codec):
        return cc.all_to_all_c(v, "model", 0, 0, c, ID)

    ag_specs = (P(("data", "model")), P("data"))
    rs_specs = (P(("data",)), P(("data", "model")))
    ar_specs = (P(("data",)), P("data"))
    pp_specs = (P(("data", "model")), P(("data", "model")))

    packed_ag = run(ag, x_ag, *ag_specs)
    with cc.multibuffer_wire():
        check_equal(f"{name}/ag_packed_vs_multibuf",
                    packed_ag, run(ag, x_ag, *ag_specs))
    check_equal(f"{name}/ag_ring_vs_monolithic",
                packed_ag, run(ag_ring, x_ag, *ag_specs))
    check_equal(f"{name}/ag_ring_serial_schedule_vs_monolithic",
                packed_ag, run(ag_ring_serial, x_ag, *ag_specs))
    check_equal(f"{name}/ag_ring_vs_monolithic_ragged",
                run(ag, x_ragged, *ag_specs),
                run(ag_ring, x_ragged, *ag_specs))

    packed_rs = run(rs, x_rs, *rs_specs)
    with cc.multibuffer_wire():
        check_equal(f"{name}/rs_packed_vs_multibuf",
                    packed_rs, run(rs, x_rs, *rs_specs))
    check_equal(f"{name}/rs_ring_vs_monolithic",
                packed_rs, run(rs_ring, x_rs, *rs_specs))
    check_equal(f"{name}/rs_ring_serial_schedule_vs_monolithic",
                packed_rs, run(rs_ring_serial, x_rs, *rs_specs))
    check_equal(f"{name}/rs_ring_vs_monolithic_ragged",
                run(rs, x_ragged, *rs_specs),
                run(rs_ring, x_ragged, *rs_specs))

    check_equal(f"{name}/allreduce_ring_vs_monolithic",
                run(ar, x_rs, *ar_specs), run(ar_ring, x_rs, *ar_specs))

    packed_pp = run(pp, x_ag, *pp_specs)
    with cc.multibuffer_wire():
        check_equal(f"{name}/ppermute_packed_vs_multibuf",
                    packed_pp, run(pp, x_ag, *pp_specs))
    packed_a2a = run(a2a, x_a2a, *pp_specs)
    with cc.multibuffer_wire():
        check_equal(f"{name}/a2a_packed_vs_multibuf",
                    packed_a2a, run(a2a, x_a2a, *pp_specs))
    # a2a with ragged trailing slots (per-peer slot size not a granule
    # multiple) and with a chunked codec (chunks= must be IGNORED on the
    # a2a hop — monolithic transport, identical bytes and results)
    def a2a_ring(v, c=ring):
        return cc.all_to_all_c(v, "model", 0, 0, c, ID)

    check_equal(f"{name}/a2a_ragged_packed_vs_multibuf",
                run(a2a, x_a2a_ragged, *pp_specs),
                _mb(a2a, x_a2a_ragged, *pp_specs))
    check_equal(f"{name}/a2a_chunked_codec_ignores_chunks",
                packed_a2a, run(a2a_ring, x_a2a, *pp_specs))

# ------------------------------------------------- gradients through rings
TACO = CODECS["taco"]
TACO_RING = with_ring(TACO)
TACO_RING_SERIAL = with_ring(TACO, schedule="serial")
TACO_ZLE = CODECS["taco_zle"]
TACO_ZLE_RING = with_ring(TACO_ZLE)
TACO_ZLE_RING_SERIAL = with_ring(TACO_ZLE, schedule="serial")
w = jnp.asarray(rng.normal(0, 0.1, (512, 64)).astype(np.float32))


def grad_of(codec):
    def loss(v):
        g = cc.all_gather_c(v, "model", 0, codec, codec)
        return jnp.sum(jnp.tanh(g @ w)) / g.size
    return run(lambda v: jax.grad(loss)(v), x_ag,
               P(("data", "model")), P(("data", "model")))


grad_mono = grad_of(TACO)
check_equal("grad/ag_ring_vs_monolithic", grad_mono, grad_of(TACO_RING))
check_equal("grad/ag_ring_serial_schedule_vs_monolithic",
            grad_mono, grad_of(TACO_RING_SERIAL))
# the lossless stage is exact: hybrid grads must equal BARE taco grads
# bit-for-bit, through every transport
check_equal("grad/hybrid_zle_vs_bare_taco", grad_mono, grad_of(TACO_ZLE))
check_equal("grad/hybrid_zle_ring_vs_bare_taco",
            grad_mono, grad_of(TACO_ZLE_RING))
check_equal("grad/hybrid_zle_ring_serial_vs_bare_taco",
            grad_mono, grad_of(TACO_ZLE_RING_SERIAL))

# --------------------------------------------------------- HLO inspection
# taco dual metadata has THREE wire components — the strongest fusion case
ag_specs = (P(("data", "model")), P("data"))
rs_specs = (P(("data",)), P(("data", "model")))
pp_specs = (P(("data", "model")), P(("data", "model")))

check_counts("hlo/ag_packed_one_collective",
             collectives_of(lambda v: cc.all_gather_c(v, "model", 0, TACO, ID),
                            x_ag, *ag_specs),
             {"all_gather": 1})
with cc.multibuffer_wire():
    check_counts("hlo/ag_multibuf_three_collectives",
                 collectives_of(
                     lambda v: cc.all_gather_c(v, "model", 0, TACO, ID),
                     x_ag, *ag_specs),
                 {"all_gather": 3})
check_counts("hlo/rs_packed_one_collective",
             collectives_of(
                 lambda v: cc.psum_scatter_c(v, "model", 0, TACO, ID),
                 x_rs, *rs_specs),
             {"all_to_all": 1})
check_counts("hlo/ppermute_packed_one_collective",
             collectives_of(
                 lambda v: cc.ppermute_c(v, "model", PERM, TACO, ID),
                 x_ag, *pp_specs),
             {"collective_permute": 1})
check_counts("hlo/a2a_packed_one_collective",
             collectives_of(
                 lambda v: cc.all_to_all_c(v, "model", 0, 0, TACO, ID),
                 x_a2a, *pp_specs),
             {"all_to_all": 1})
check_counts("hlo/ag_ring_chunked_permutes",
             collectives_of(
                 lambda v: cc.all_gather_c(v, "model", 0, TACO_RING, ID),
                 x_ag, *ag_specs),
             {"collective_permute": CHUNKS * (TP - 1)})
check_counts("hlo/ag_ring_serial_schedule_chunked_permutes",
             collectives_of(
                 lambda v: cc.all_gather_c(v, "model", 0, TACO_RING_SERIAL,
                                           ID),
                 x_ag, *ag_specs),
             {"collective_permute": CHUNKS * (TP - 1)})
check_counts("hlo/rs_ring_chunked_permutes",
             collectives_of(
                 lambda v: cc.psum_scatter_c(v, "model", 0, TACO_RING, ID),
                 x_rs, *rs_specs),
             {"collective_permute": CHUNKS * (TP - 1)})

# hybrid variable-layout hops: STILL exactly one lax collective moving
# the (bounded) packed buffer; multibuffer moves length+bitmap+data
check_counts("hlo/hybrid_zle_ag_packed_one_collective",
             collectives_of(
                 lambda v: cc.all_gather_c(v, "model", 0, TACO_ZLE, ID),
                 x_ag, *ag_specs),
             {"all_gather": 1})
check_counts("hlo/hybrid_zle_rs_packed_one_collective",
             collectives_of(
                 lambda v: cc.psum_scatter_c(v, "model", 0, TACO_ZLE, ID),
                 x_rs, *rs_specs),
             {"all_to_all": 1})
check_counts("hlo/hybrid_zle_a2a_packed_one_collective",
             collectives_of(
                 lambda v: cc.all_to_all_c(v, "model", 0, 0, TACO_ZLE, ID),
                 x_a2a, *pp_specs),
             {"all_to_all": 1})
check_counts("hlo/hybrid_zle_a2a_chunked_codec_still_one_collective",
             collectives_of(
                 lambda v: cc.all_to_all_c(v, "model", 0, 0, TACO_ZLE_RING,
                                           ID),
                 x_a2a, *pp_specs),
             {"all_to_all": 1})
with cc.multibuffer_wire():
    check_counts("hlo/hybrid_zle_ag_multibuf_three_collectives",
                 collectives_of(
                     lambda v: cc.all_gather_c(v, "model", 0, TACO_ZLE, ID),
                     x_ag, *ag_specs),
                 {"all_gather": 3})   # length + bitmap + data
check_counts("hlo/hybrid_zle_ag_ring_chunked_permutes",
             collectives_of(
                 lambda v: cc.all_gather_c(v, "model", 0, TACO_ZLE_RING, ID),
                 x_ag, *ag_specs),
             {"collective_permute": CHUNKS * (TP - 1)})

# ------------------------------------- HLO structure of the ring schedules
# Lowered StableHLO preserves emission order, so textual positions show
# which stage ordering was emitted; the optimization_barrier fences are
# what then FORBID the compiler from re-serializing it.  Encode marker:
# every taco encode computes per-block amax scales -> stablehlo.reduce
# (the AG decode path has none, so reduces between ppermutes can only
# come from interleaved encodes).


def _positions(txt, token):
    return [m.start() for m in re.finditer(re.escape(token), txt)]


def _between(positions, lo, hi):
    return sum(1 for pos in positions if lo < pos < hi)


txt_pipe = lowered_text(
    lambda v: cc.all_gather_c(v, "model", 0, TACO_RING, ID), x_ag, *ag_specs)
txt_ser = lowered_text(
    lambda v: cc.all_gather_c(v, "model", 0, TACO_RING_SERIAL, ID),
    x_ag, *ag_specs)
for sched, txt in (("pipelined", txt_pipe), ("serial", txt_ser)):
    perm = _positions(txt, "stablehlo.collective_permute")
    bar = _positions(txt, "stablehlo.optimization_barrier")
    enc = _positions(txt, "stablehlo.reduce")
    enc_mid = _between(enc, perm[0], perm[-1])
    bar_mid = _between(bar, perm[0], perm[-1])
    if sched == "pipelined":
        # at least the steady-state encodes (chunks 2..N-1) land between
        # ring steps, every tick is fenced, and fences sit between steps
        want_bar = CHUNKS + 2
        check_true("hlo/ag_ring_pipelined_interleaves_encodes",
                   enc_mid >= CHUNKS - 2 and len(bar) == want_bar
                   and bar_mid >= 1,
                   f"encodes_between_permutes={enc_mid} "
                   f"barriers={len(bar)} (want {want_bar}) "
                   f"barriers_between_permutes={bar_mid}")
    else:
        check_true("hlo/ag_ring_serial_hoists_encodes",
                   enc_mid == 0 and not bar,
                   f"encodes_between_permutes={enc_mid} (want 0) "
                   f"barriers={len(bar)} (want 0)")

# ------------------------------------------ negotiated (slot=auto) hops
# padded workload: the trailing 75% of every wire row is zero (sequence
# padding), so the controller negotiates a genuinely smaller bound
x_pad_np = rng.normal(0, 0.02, (16, 512)).astype(np.float32)
x_pad_np[:, 128:] = 0.0
x_pad = jnp.asarray(x_pad_np)

for suffix in ("", f":chunks={CHUNKS}", f":chunks={CHUNKS}:schedule=serial"):
    label = "negotiated" + (suffix.replace(":", "_") or "_packed")
    auto = codec_from_spec("taco+zle:jnp:slot=auto" + suffix)
    static = codec_from_spec("taco+zle:jnp" + suffix)
    ctl = cc.SlotController()

    def ag_s(v, c=static):
        return cc.all_gather_c(v, "model", 0, c, ID)

    def rs_s(v, c=static):
        return cc.psum_scatter_c(v, "model", 0, c, ID)

    # bootstrap step: the un-negotiated auto codec runs against the full
    # static bound while its probes observe the REAL per-device chunk
    # geometry (the ring flattens each device's local block before
    # chunking, so a host-side guess at the chunk contents would
    # mis-predict which chunks carry the dense columns)
    boot_ag = run(lambda v: cc.all_gather_c(v, "model", 0, auto, ID),
                  x_pad, *ag_specs)
    boot_rs = run(lambda v: cc.psum_scatter_c(v, "model", 0, auto, ID),
                  x_pad, *rs_specs)
    assert not ctl.finish_step()          # static bounds cannot overflow
    neg = ctl.negotiate(auto)
    moved = cc.moved_slot_bytes(neg, x_pad.shape[-1])
    slot = cc.wire_slot_bytes(auto, x_pad.shape[-1])
    check_true(f"{label}/moved_below_slot", moved < slot,
               f"moved={moved} slot={slot} "
               f"({moved / slot:.3f}x, frac={neg.moved_frac})")

    def ag_n(v, c=neg):
        return cc.all_gather_c(v, "model", 0, c, ID)

    def rs_n(v, c=neg):
        return cc.psum_scatter_c(v, "model", 0, c, ID)

    base_ag = run(ag_s, x_pad, *ag_specs)
    base_rs = run(rs_s, x_pad, *rs_specs)
    check_equal(f"{label}/ag_bootstrap_vs_static", base_ag, boot_ag)
    check_equal(f"{label}/rs_bootstrap_vs_static", base_rs, boot_rs)
    check_equal(f"{label}/ag_vs_static_bound",
                base_ag, run(ag_n, x_pad, *ag_specs))
    check_equal(f"{label}/rs_vs_static_bound",
                base_rs, run(rs_n, x_pad, *rs_specs))
    check_true(f"{label}/no_overflow_on_observed_workload",
               not ctl.finish_step(),
               f"overflows={ctl.overflows}")
    if not suffix:
        check_counts(f"{label}/hlo_ag_one_collective",
                     collectives_of(ag_n, x_pad, *ag_specs),
                     {"all_gather": 1})
        check_counts(f"{label}/hlo_rs_one_collective",
                     collectives_of(rs_n, x_pad, *rs_specs),
                     {"all_to_all": 1})
    else:
        check_counts(f"{label}/hlo_ag_ring_chunked_permutes",
                     collectives_of(ag_n, x_pad, *ag_specs),
                     {"collective_permute": CHUNKS * (TP - 1)})

# the ring reduce-scatter gathers its per-peer sends ONCE per chunk
# before the step loop (static row slices inside it): zero dynamic-slices
# of the wire matrix re-materialized per step, under either schedule
for sched, codec in (("pipelined", TACO_RING), ("serial",
                                                TACO_RING_SERIAL)):
    txt = lowered_text(
        lambda v: cc.psum_scatter_c(v, "model", 0, codec, ID),
        x_rs, *rs_specs)
    n_dyn = len(_positions(txt, "stablehlo.dynamic_slice"))
    check_true(f"hlo/rs_ring_{sched}_hoisted_sends_no_dynamic_slice",
               n_dyn == 0, f"dynamic_slices={n_dyn} (want 0)")
# multibuffer_wire() restores the FULL pre-packing engine: chunked codecs
# fall back to the monolithic multi-buffer transport, no ring permutes
with cc.multibuffer_wire():
    check_counts("hlo/ring_disabled_under_multibuffer_wire",
                 collectives_of(
                     lambda v: cc.all_gather_c(v, "model", 0, TACO_RING, ID),
                     x_ag, *ag_specs),
                 {"all_gather": 3})

# ----------------------- transposed (Ulysses) all-to-all layout matrix
# split_dim=2 (heads), concat_dim=1 (sequence): the heads<->sequence
# redistribute of the sequence-parallel attention path.  Sequence dim
# sharded over the 4-way model axis on the way in, heads on the way out.
x_u = jnp.asarray(rng.normal(0, 0.02, (4, 8, 16, 6)).astype(np.float32))
u_in = (P(None, "model"), P(None, None, "model"))       # seq -> heads
u_out = (P(None, None, "model"), P(None, "model"))      # heads -> seq


def a2a_t(v, c):
    return cc.all_to_all_c(v, "model", 2, 1, c, ID)


def a2a_t_inv(v, c):
    return cc.all_to_all_c(v, "model", 1, 2, c, ID)


def a2a_t_flat_ref(v, c):
    """The transposed hop's value reference: run the SAME codec through
    the flat equal-dims transport (parity-pinned above) on the moved
    layout, then rearrange with the tiled-layout algebra — which the
    identity rows pin against raw ``lax.all_to_all`` below, so a layout
    bug in the implementation cannot also hide here."""
    moved = jnp.moveaxis(v, 2, 0)
    flat = cc.all_to_all_c(moved.reshape(TP * 4, -1), "model", 0, 0, c, ID)
    stack = flat.reshape(TP, 4, *moved.shape[1:])
    out = jnp.moveaxis(jnp.moveaxis(stack, 1, 3), 0, 1)
    shape = list(v.shape)
    shape[2] //= TP
    shape[1] *= TP
    return out.reshape(shape)


# identity codec: bit-parity with raw lax.all_to_all, both directions,
# and the round trip is the identity
nat_fwd = run(lambda v: jax.lax.all_to_all(v, "model", 2, 1, tiled=True),
              x_u, *u_in)
got_fwd = run(lambda v: a2a_t(v, ID), x_u, *u_in)
check_equal("a2a_transposed/identity_vs_native_fwd", got_fwd, nat_fwd)
check_equal("a2a_transposed/identity_vs_native_inv",
            run(lambda v: a2a_t_inv(v, ID), nat_fwd, *u_out),
            run(lambda v: jax.lax.all_to_all(v, "model", 1, 2, tiled=True),
                nat_fwd, *u_out))
check_equal("a2a_transposed/identity_roundtrip",
            run(lambda v: a2a_t_inv(a2a_t(v, ID), ID), x_u,
                u_in[0], u_in[0]), x_u)
# the flat-reference rearrangement itself, pinned at identity vs native
check_equal("a2a_transposed/flat_ref_vs_native_identity",
            run(lambda v: a2a_t_flat_ref(v, ID), x_u, *u_in), nat_fwd)

for name, codec in CODECS.items():
    got = run(lambda v, c=codec: a2a_t(v, c), x_u, *u_in)
    check_equal(f"{name}/a2a_transposed_vs_flat_transport",
                got, run(lambda v, c=codec: a2a_t_flat_ref(v, c),
                         x_u, *u_in))
    check_equal(f"{name}/a2a_transposed_packed_vs_multibuf",
                got, _mb(lambda v, c=codec: a2a_t(v, c), x_u, *u_in))
    check_equal(f"{name}/a2a_transposed_chunked_codec_ignores_chunks",
                got, run(lambda v, c=with_ring(codec): a2a_t(v, c),
                         x_u, *u_in))

# gradients: the custom_vjp bwd of a transposed a2a is the INVERSE
# redistribute with swapped codecs — identity grads must match native
# lax.all_to_all grads bit-for-bit; compressed cotangents must equal the
# explicit inverse hop applied to the upstream cotangent
w_u = jnp.asarray(rng.normal(0, 0.1, (6,)).astype(np.float32))


def grad_t(fn):
    def loss(v):
        y = fn(v)
        return jnp.sum(jnp.tanh(y @ w_u))
    return run(lambda v: jax.grad(loss)(v), x_u, u_in[0], u_in[0])


check_equal("grad/a2a_transposed_identity_vs_native",
            grad_t(lambda v: a2a_t(v, ID)),
            grad_t(lambda v: jax.lax.all_to_all(v, "model", 2, 1,
                                                tiled=True)))
ct_u = jnp.asarray(rng.normal(0, 0.02, (4, 8, 16, 6)).astype(np.float32))


def _vjp_taco(v, ct):
    _, f = jax.vjp(lambda a: cc.all_to_all_c(a, "model", 2, 1, TACO,
                                             CODECS["sdp4bit"]), v)
    return f(ct)[0]
check_equal("grad/a2a_transposed_bwd_is_swapped_inverse_hop",
            jit_sm(_vjp_taco, (u_in[0], u_in[1]), u_in[0])(x_u, ct_u),
            run(lambda c: cc.all_to_all_c(c, "model", 1, 2,
                                          CODECS["sdp4bit"], TACO),
                ct_u, u_in[1], u_in[0]))

# HLO: ONE all-to-all per compressed transposed hop (taco AND the
# variable-layout hybrid), one per wire component under multibuffer
check_counts("hlo/a2a_transposed_packed_one_collective",
             collectives_of(lambda v: a2a_t(v, TACO), x_u, *u_in),
             {"all_to_all": 1})
check_counts("hlo/hybrid_zle_a2a_transposed_one_collective",
             collectives_of(lambda v: a2a_t(v, TACO_ZLE), x_u, *u_in),
             {"all_to_all": 1})
with cc.multibuffer_wire():
    check_counts("hlo/a2a_transposed_multibuf_three_collectives",
                 collectives_of(lambda v: a2a_t(v, TACO), x_u, *u_in),
                 {"all_to_all": 3})

# negotiated (slot=auto) transposed a2a: bootstrap -> negotiate -> the
# negotiated bound moves strictly fewer bytes, stays bit-identical to
# the static bound, never overflows, and still lowers to ONE all-to-all
# heads 1-3 of every group of 4 are zero: the head dim is the a2a split
# dim, so every peer slot's wire buffer ends in a contiguous 3/4 zero
# run; hd is sized so each slot spans several codec granule groups and
# the zero tail covers whole groups (the ASH transform mixes only
# within a group) — otherwise the lossless stage has nothing to compact
x_u_pad_np = rng.normal(0, 0.02, (4, 8, 16, 48)).astype(np.float32)
x_u_pad_np[:, :, np.arange(16) % 4 != 0, :] = 0.0
x_u_pad = jnp.asarray(x_u_pad_np)
auto_u = codec_from_spec("taco+zle:jnp:slot=auto")
static_u = codec_from_spec("taco+zle:jnp")
ctl_u = cc.SlotController()
boot_u = run(lambda v: a2a_t(v, auto_u), x_u_pad, *u_in)
assert not ctl_u.finish_step()
neg_u = ctl_u.negotiate(auto_u)
# local elems (sequence dim sharded TP ways) split into TP peer slots
slot_elems = x_u_pad.size // (TP * TP)
moved_u = cc.moved_slot_bytes(neg_u, slot_elems)
slot_u = cc.wire_slot_bytes(auto_u, slot_elems, chunks=1)
check_true("negotiated_a2a_transposed/moved_below_slot",
           moved_u < slot_u, f"moved={moved_u} slot={slot_u}")
base_u = run(lambda v: a2a_t(v, static_u), x_u_pad, *u_in)
check_equal("negotiated_a2a_transposed/bootstrap_vs_static", base_u, boot_u)
check_equal("negotiated_a2a_transposed/negotiated_vs_static_bound",
            base_u, run(lambda v: a2a_t(v, neg_u), x_u_pad, *u_in))
check_true("negotiated_a2a_transposed/no_overflow",
           not ctl_u.finish_step(), f"overflows={ctl_u.overflows}")
check_counts("negotiated_a2a_transposed/hlo_one_collective",
             collectives_of(lambda v: a2a_t(v, neg_u), x_u_pad, *u_in),
             {"all_to_all": 1})

if FAILURES:
    raise SystemExit(f"FAILED: {FAILURES}")
print("ALL TRANSPORT PARITY CHECKS PASSED")
