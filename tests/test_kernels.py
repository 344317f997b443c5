"""Per-kernel allclose sweeps: Pallas (interpret mode) vs ref.py oracle.

Sweeps shapes x dtypes x formats per the deliverable (c) requirement.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ash
from repro.core.taco import TacoConfig
from repro.kernels import ops, ref

from conftest import tp_like


def cfgs(**kw):
    base = dict(impl="pallas_interpret")
    base.update(kw)
    p = TacoConfig(**base)
    j = TacoConfig(**{**base, "impl": "jnp"})
    return p, j


SHAPES = [(1, 256), (7, 256), (128, 256), (300, 256), (16, 64), (33, 512)]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("in_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("fmt", ["e4m3", "e5m2", "int8"])
def test_compress_kernel_matches_ref(shape, in_dtype, fmt, rng):
    m, b = shape
    x = jnp.asarray(tp_like(rng, shape)).astype(in_dtype)
    cp, cj = cfgs(block_size=b, fmt=fmt)
    qp, ap, sp = ops.compress_blocks(x, cp)
    qj, aj, sj = ref.compress_blocks_ref(x, cj)
    assert qp.shape == (m, b) and ap.shape == (m,) and sp.shape == (m, 1)
    np.testing.assert_allclose(np.asarray(ap), np.asarray(aj), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sj), rtol=1e-5)
    # payloads: same quantization grid; tolerate 1-ULP disagreement from
    # fp reassociation at grid boundaries
    pf = np.asarray(qp.astype(jnp.float32))
    jf = np.asarray(qj.astype(jnp.float32))
    mism = np.mean(pf != jf)
    assert mism < 0.01, f"payload mismatch fraction {mism}"


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("fmt", ["e4m3", "int8"])
@pytest.mark.parametrize("folded", [False, True])
def test_decompress_kernel_matches_ref(shape, fmt, folded, rng):
    m, b = shape
    x = jnp.asarray(tp_like(rng, shape))
    cp, cj = cfgs(block_size=b, fmt=fmt)
    q, a, s = ref.compress_blocks_ref(x, cj)
    if folded:
        s_in, a_in = s / a[:, None], None
    else:
        s_in, a_in = s, a
    dp = ops.decompress_blocks(q, s_in, a_in, cp)
    dj = ref.decompress_blocks_ref(q, s_in, a_in, cj)
    np.testing.assert_allclose(np.asarray(dp), np.asarray(dj),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("peers", [1, 2, 4, 16])
@pytest.mark.parametrize("shape", [(8, 256), (130, 256), (5, 128)])
def test_decompress_reduce_kernel_matches_ref(peers, shape, rng):
    m, b = shape
    cp, cj = cfgs(block_size=b)
    qs, ss, aas = [], [], []
    for p in range(peers):
        x = jnp.asarray(tp_like(rng, shape))
        q, a, s = ref.compress_blocks_ref(x, cj)
        qs.append(q); ss.append(s); aas.append(a)
    q = jnp.stack(qs); s = jnp.stack(ss); a = jnp.stack(aas)
    want = ref.decompress_reduce_ref(q, s, a, cj)
    got_pallas = ops.decompress_reduce(q, s, a, cp)
    got_jnp = ops.decompress_reduce(q, s, a, cj)
    np.testing.assert_allclose(np.asarray(got_pallas), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_jnp), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_quant_group_size_kernel(rng):
    x = jnp.asarray(tp_like(rng, (64, 256)))
    cp, cj = cfgs(quant_group_size=32)
    qp, ap, sp = ops.compress_blocks(x, cp)
    qj, aj, sj = ref.compress_blocks_ref(x, cj)
    assert sp.shape == (64, 8)
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sj), rtol=1e-5)
    dp = ops.decompress_blocks(qp, sp, ap, cp)
    dj = ref.decompress_blocks_ref(qj, sj, aj, cj)
    np.testing.assert_allclose(np.asarray(dp), np.asarray(dj),
                               rtol=1e-3, atol=1e-6)


@pytest.mark.parametrize("scale_eps", [1e-30, 1e-20, 1e-6])
def test_scale_floor_parity_zero_and_denormal_blocks(scale_eps):
    """The dual-scale floor is ONE cfg-derived value (cfg.scale_eps)
    routed through both the Pallas kernel and the jnp ref — all-zero and
    denormal blocks must quantize identically on both paths (the kernel
    used to hardcode 1e-30 while quantize_ds took a configurable eps)."""
    zero = jnp.zeros((4, 256), jnp.float32)
    denormal = jnp.full((4, 256), 1e-38, jnp.float32)
    mixed = jnp.concatenate([zero, denormal,
                             jnp.linspace(-1e-35, 1e-35, 256)[None, :]])
    for x in (zero, denormal, mixed):
        cp, cj = cfgs(scale_eps=scale_eps)
        qp, ap, sp = ops.compress_blocks(x, cp)
        qj, aj, sj = ref.compress_blocks_ref(x, cj)
        np.testing.assert_array_equal(np.asarray(sp), np.asarray(sj))
        np.testing.assert_array_equal(np.asarray(ap), np.asarray(aj))
        np.testing.assert_array_equal(
            np.asarray(qp.astype(jnp.float32)),
            np.asarray(qj.astype(jnp.float32)))
        # floor applied: no zero scales anywhere (f32-rounded floor)
        assert float(jnp.min(sp)) >= float(np.float32(scale_eps))
        # decode side agrees too (zero blocks must decode to exact zeros)
        dp = ops.decompress_blocks(qp, sp, ap, cp)
        dj = ref.decompress_blocks_ref(qj, sj, aj, cj)
        np.testing.assert_array_equal(np.asarray(dp), np.asarray(dj))
        if x is zero:   # zero blocks round-trip to exact zeros
            assert float(jnp.max(jnp.abs(dp))) == 0.0


def test_scale_floor_routed_through_wire_kernel():
    """The fused wire-emission kernel uses the same cfg.scale_eps floor:
    scales inside the packed buffer match the block kernel's bit-for-bit
    on degenerate blocks."""
    from repro.core.registry import codec_from_spec
    from repro.core.codecs import pack_wire
    codec = codec_from_spec("taco:pallas_interpret:seps1e-20")
    assert codec.cfg.scale_eps == 1e-20
    x = jnp.zeros((2, 512), jnp.float32)
    want = pack_wire(codec.encode(x), codec.wire_layout(512))
    np.testing.assert_array_equal(np.asarray(codec.encode_wire(x)),
                                  np.asarray(want))


def test_kernel_fallback_for_unsupported_config(rng, monkeypatch):
    """Ablation configs (plain hadamard / per-tensor scale) take the jnp
    path only under impl='auto'; an explicit kernel impl raises instead
    of silently running the reference."""
    x = jnp.asarray(tp_like(rng, (4, 256)))
    for kw in (dict(transform="hadamard"), dict(scale_granularity="tensor")):
        for impl in ("pallas", "pallas_interpret"):
            with pytest.raises(ValueError, match="has no kernel"):
                ops.compress_blocks(x, TacoConfig(impl=impl, **kw))
        q, a, s = ops.compress_blocks(x, TacoConfig(impl="auto", **kw))
        assert q.shape == (4, 256)
    # the auto rule as a TPU host sees it: kernels for the production
    # config, the reference for ablations
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._impl_for(TacoConfig()) == "pallas"
    assert ops._impl_for(TacoConfig(transform="hadamard")) == "jnp"


def test_end_to_end_error_tiny_vs_direct_cast(rng):
    """Full fused pipeline beats naive FP8 cast on TP-like data (the reason
    the paper exists)."""
    x = jnp.asarray(tp_like(rng, (256, 256), scale=1e-4, tail=1.0))
    cfg = TacoConfig(impl="pallas_interpret")
    q, a, s = ops.compress_blocks(x, cfg)
    xh = ops.decompress_blocks(q, s, a, cfg)
    taco_err = float(jnp.linalg.norm(xh - x) / jnp.linalg.norm(x))
    naive = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    naive_err = float(jnp.linalg.norm(naive - x) / jnp.linalg.norm(x))
    assert taco_err < naive_err * 0.5, (taco_err, naive_err)
