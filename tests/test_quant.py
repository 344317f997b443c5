"""Dual-scale quantization tests (paper §3, §4.3)."""
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline container — seeded-sampling shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import quant
from repro.core.taco import TacoConfig, compress, decompress, wire_bytes, raw_bytes

from conftest import tp_like

@pytest.mark.parametrize("fmt", ["e4m3", "e5m2", "int8"])
def test_quantize_within_range(fmt, rng):
    spec = quant.FORMATS[fmt]
    z = jnp.asarray(tp_like(rng, (16, 256)))
    q, s = quant.quantize_ds(z, spec)
    qf = np.asarray(q.astype(jnp.float32))
    assert np.all(np.abs(qf) <= spec.qmax * (1 + 1e-6))
    assert np.all(np.isfinite(qf))
    assert s.shape == (16, 1)


@pytest.mark.parametrize("fmt", ["e4m3", "e5m2", "int8"])
@pytest.mark.parametrize("gs", [32, 64, 256])
def test_roundtrip_error_bounded(fmt, gs, rng):
    spec = quant.FORMATS[fmt]
    z = jnp.asarray(rng.normal(0, 1.0, (8, 256)).astype(np.float32))
    q, s = quant.quantize_ds(z, spec, group_size=gs)
    zh = np.asarray(quant.dequantize_ds(q, s, spec))
    # max-scaled 8-bit formats: worst-case relative-to-range error
    step = {"e4m3": 1 / 16, "e5m2": 1 / 8, "int8": 1 / 127}[fmt]
    smax = np.repeat(np.asarray(s), gs, axis=-1).reshape(8, 256) * spec.qmax
    assert np.all(np.abs(zh - np.asarray(z)) <= smax * step + 1e-7)


def test_zero_tensor_stable():
    cfg = TacoConfig(impl="jnp")
    x = jnp.zeros((4, 256), jnp.float32)
    c = compress(x, cfg)
    xh = decompress(c, cfg, shape=x.shape, dtype=x.dtype)
    assert np.all(np.isfinite(np.asarray(xh)))
    np.testing.assert_allclose(np.asarray(xh), 0.0, atol=1e-6)


def test_fp8_beats_int8_on_near_zero_heavy_tail(rng):
    """Paper §3 core claim: for zero-concentrated long-tail data WITHOUT
    pre-conditioning, FP8's exponential grid loses far less of the dense
    near-zero mass than INT8's uniform grid (element-wise relative error
    on the small-magnitude subset)."""
    x = tp_like(rng, (32, 256), outlier_frac=0.01, scale=0.005, tail=3.0)
    xj = jnp.asarray(x)
    errs = {}
    for fmt in ["e4m3", "int8"]:
        cfg = TacoConfig(fmt=fmt, transform="none", impl="jnp")
        c = compress(xj, cfg)
        xh = np.asarray(decompress(c, cfg, shape=x.shape, dtype=jnp.float32))
        small = np.abs(x) < 0.01
        denom = np.maximum(np.abs(x[small]), 1e-4)
        errs[fmt] = np.mean(np.abs(xh[small] - x[small]) / denom)
    assert errs["e4m3"] < errs["int8"]


def test_compression_ratio(rng):
    x = jnp.asarray(tp_like(rng, (1024, 1024)))  # bf16-sized payloads in prod
    for meta, lo in [("dual", 3.7), ("folded", 3.8)]:
        cfg = TacoConfig(metadata=meta, impl="jnp")
        c = compress(x.astype(jnp.float32), cfg)
        # vs bf16 on the wire (2 bytes/elem), ratio ~ 2x minus metadata
        ratio = (x.size * 2) / wire_bytes(c)
        assert ratio > lo / 2, (meta, ratio)


def test_folded_metadata_bit_identical(rng):
    """DESIGN.md §7.1: alpha cancels when s is max-based at block-or-finer
    granularity — folded single-scale metadata reconstructs identically."""
    x = jnp.asarray(tp_like(rng, (8, 2048)))
    for gs in [None, 64]:
        cd = TacoConfig(metadata="dual", quant_group_size=gs, impl="jnp")
        cf = TacoConfig(metadata="folded", quant_group_size=gs, impl="jnp")
        xd = decompress(compress(x, cd), cd, shape=x.shape, dtype=jnp.float32)
        xf = decompress(compress(x, cf), cf, shape=x.shape, dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(xd), np.asarray(xf),
                                   rtol=1e-4, atol=1e-5)


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    scale=st.floats(1e-5, 1e2),
    fmt=st.sampled_from(["e4m3", "e5m2"]),
)
def test_property_compress_error_bound(seed, scale, fmt):
    """relRMSE of full TACO roundtrip stays within format resolution for
    Gaussian blocks (rotation makes blocks Gaussian-like; max-scale then
    bounds relative error by ~ULP * dynamic headroom)."""
    r = np.random.default_rng(seed)
    x = jnp.asarray((r.normal(size=(16, 256)) * scale).astype(np.float32))
    cfg = TacoConfig(fmt=fmt, impl="jnp")
    c = compress(x, cfg)
    xh = decompress(c, cfg, shape=x.shape, dtype=jnp.float32)
    rel = float(jnp.linalg.norm(xh - x) / (jnp.linalg.norm(x) + 1e-30))
    assert rel < {"e4m3": 0.06, "e5m2": 0.12}[fmt]
