"""Property-based tests (hypothesis) on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline container — seeded-sampling shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import dp_compress, pp_compress
from repro.core.taco import TacoConfig, compress, decompress
from repro.configs import ASSIGNED, get_config, make_plan
from repro.configs.base import smoke_config


@settings(max_examples=40, deadline=None)
@given(
    n=st.integers(1, 5000),
    seed=st.integers(0, 2**31 - 1),
    scale=st.floats(1e-6, 1e4),
    fmt=st.sampled_from(["e4m3", "e5m2", "int8"]),
    meta=st.sampled_from(["dual", "folded"]),
)
def test_compress_any_shape_roundtrips(n, seed, scale, fmt, meta):
    """compress/decompress must handle arbitrary tensor sizes (padding) and
    scales without NaN/Inf, with bounded relative error."""
    r = np.random.default_rng(seed)
    x = jnp.asarray((r.normal(size=n) * scale).astype(np.float32))
    cfg = TacoConfig(fmt=fmt, metadata=meta, impl="jnp")
    xh = decompress(compress(x, cfg), cfg, shape=x.shape, dtype=x.dtype)
    assert np.all(np.isfinite(np.asarray(xh)))
    rel = float(jnp.linalg.norm(xh - x) / (jnp.linalg.norm(x) + 1e-30))
    assert rel < 0.25


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), m=st.integers(1, 8))
def test_int4_pack_unpack_property(seed, m):
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.integers(-8, 8, (m, 128)).astype(np.int8))
    back = dp_compress.int4_unpack(dp_compress.int4_pack(q))
    np.testing.assert_array_equal(np.asarray(back), np.asarray(q))


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    block=st.sampled_from([32, 64, 128]),
    rotate=st.booleans(),
)
def test_int4_error_bounded(seed, block, rotate):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(2, 1024)).astype(np.float32))
    packed, s = dp_compress.compress_int4(x, block, rotate)
    back = dp_compress.decompress_int4(packed, s, 1024, block, rotate,
                                       jnp.float32)
    # int4 with per-block max scale: |err| <= s_max/2 per element pre-
    # rotation; keep a loose-but-meaningful norm bound
    rel = float(jnp.linalg.norm(back - x) / jnp.linalg.norm(x))
    assert rel < 0.25
    assert np.all(np.isfinite(np.asarray(back)))


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), group=st.sampled_from([32, 64, 128]))
def test_int8_group_error_bounded(seed, group):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(3, 512)).astype(np.float32))
    q, s = pp_compress.compress_int8_group(x, group)
    back = pp_compress.decompress_int8_group(q, s, 512, group, jnp.float32)
    err = np.abs(np.asarray(back) - np.asarray(x))
    bound = np.repeat(np.asarray(s), group, axis=-1).reshape(3, 512) * 0.5 + 1e-7
    assert np.all(err <= bound)


@settings(max_examples=15, deadline=None)
@given(
    tp=st.sampled_from([1, 2, 4, 8, 16]),
    arch=st.sampled_from(ASSIGNED),
)
def test_plan_invariants(tp, arch):
    """RunPlan must keep heads/vocab/dff consistent for every arch x tp."""
    cfg = get_config(arch)
    plan = make_plan(cfg, tp, fsdp=2 * tp)
    assert plan.heads_pad % tp == 0
    assert plan.q_local * tp == plan.heads_pad
    if cfg.family != "rwkv":
        assert plan.heads_pad >= cfg.n_heads
        if plan.kv_mode == "sharded":
            assert plan.kv_local * tp == plan.kv_pad
            assert plan.kv_pad >= cfg.n_kv_heads
            # GQA group mapping stays device-local
            assert plan.heads_pad % plan.kv_pad == 0
        else:
            assert plan.kv_local == cfg.n_kv_heads
    assert plan.vocab_pad >= cfg.vocab_size
    assert plan.vocab_pad % tp == 0
    assert (cfg.d_ff % tp == 0) and plan.dff_local * tp == cfg.d_ff


@settings(max_examples=10, deadline=None)
@given(arch=st.sampled_from(ASSIGNED))
def test_smoke_config_same_family(arch):
    cfg = get_config(arch)
    sm = smoke_config(cfg)
    assert sm.family == cfg.family
    assert (sm.moe is None) == (cfg.moe is None)
    assert (sm.ssm is None) == (cfg.ssm is None)
    assert (sm.window is None) == (cfg.window is None)
    assert sm.param_count < cfg.param_count
