"""Ahead-of-time compiles of the TACO kernels for a described TPU v5e.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
block shapes off the (8, 128) tiling, layouts XLA and Mosaic disagree on,
unsupported reshapes and bit-width-changing bitcasts.  Each case here
compiles a kernel of the training path, at a real hop size, for one chip
of a ``v5e:2x2`` topology that is described but not attached, and checks
the kernel reached the compiled HLO (``tpu_custom_call``).

The topology is described inside a module-scoped fixture — never at
import — so that under several pytest-xdist workers only the worker that
runs this file loads the TPU compiler; where it cannot be described the
tests skip.  JAX's persistent compilation cache is off around these
compiles (an executable for a described chip cannot be read back here).
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.registry import codec_from_spec
from repro.core.taco import TacoConfig
from repro.kernels import ash_compress, ash_decompress
from repro.kernels.fwht_butterfly import compress_blocks_butterfly

# A 4M-element TP hop: 4096 tokens x d_model 1024 per chip (gpt-6.7b at
# tp=4 moves 4096 x 4096 / 4 per all-gather slot), in 256-wide blocks.
BLOCKS, BLOCK, PEERS = 16384, 256, 4
# The codec wire paths at a 1M-element hop (4 slots of 256K): XLA's TPU
# compile of the pack/unpack byte relayouts grows to tens of seconds at
# 4M, and the kernels inside are the ones compiled at 4M above.
WIRE_SLOT = 1 << 18
METAS = ["dual", "folded"]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def sds(topo):
    """``sds(shape, dtype)`` -> a ShapeDtypeStruct on one described chip."""
    from jax.sharding import SingleDeviceSharding
    chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=chip)


def kernels_in(fn, *args) -> int:
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


def cfg_for(meta, **kw):
    return TacoConfig(impl="pallas", metadata=meta, **kw)


@pytest.mark.parametrize("in_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("meta", METAS)
def test_compress_blocks_compiles(sds, meta, in_dtype):
    cfg = cfg_for(meta)
    n = kernels_in(lambda x: ash_compress.compress_blocks_pallas(x, cfg),
                   sds((BLOCKS, BLOCK), in_dtype))
    assert n == 1


@pytest.mark.parametrize("meta", METAS)
def test_decompress_blocks_compiles(sds, meta):
    cfg = cfg_for(meta)
    q = sds((BLOCKS, BLOCK), cfg.format_spec.dtype)
    s = sds((BLOCKS, 1), jnp.float32)
    if meta == "folded":
        n = kernels_in(lambda q, s: ash_decompress.decompress_blocks_pallas(
            q, s, None, cfg), q, s)
    else:
        n = kernels_in(lambda q, s, a: ash_decompress.decompress_blocks_pallas(
            q, s, a, cfg), q, s, sds((BLOCKS,), jnp.float32))
    assert n == 1


@pytest.mark.parametrize("meta", METAS)
def test_decompress_reduce_compiles(sds, meta):
    cfg = cfg_for(meta)
    m = BLOCKS // PEERS
    q = sds((PEERS, m, BLOCK), cfg.format_spec.dtype)
    s = sds((PEERS, m, 1), jnp.float32)
    if meta == "folded":
        n = kernels_in(lambda q, s: ash_decompress.decompress_reduce_pallas(
            q, s, None, cfg), q, s)
    else:
        n = kernels_in(lambda q, s, a: ash_decompress.decompress_reduce_pallas(
            q, s, a, cfg), q, s, sds((PEERS, m), jnp.float32))
    assert n == 1


@pytest.mark.parametrize("meta", METAS)
def test_codec_wire_paths_compile(sds, meta):
    """The transport's entry points on the TPU impl: encode_wire /
    decode_wire / decode_sum_wire run the block kernels + pack/unpack
    (the fused wire kernels are interpret-only, see ops.wire_kernel_impl)."""
    spec = "taco:pallas" + (":folded" if meta == "folded" else "")
    codec = codec_from_spec(spec)
    n = WIRE_SLOT
    total = codec.wire_layout(n).total_bytes
    assert kernels_in(codec.encode_wire,
                      sds((PEERS, n), jnp.bfloat16)) == 1
    assert kernels_in(lambda w: codec.decode_wire(w, n, jnp.bfloat16),
                      sds((PEERS, total), jnp.uint8)) == 1
    assert kernels_in(lambda w: codec.decode_sum_wire(w, n, jnp.bfloat16),
                      sds((PEERS, total), jnp.uint8)) == 1


def test_grouped_scale_kernels_compile(sds):
    """Finer-than-block scales (``g32``): the grouped max must not need a
    (R, B) -> (R, G, B/G) reshape, which Mosaic refuses."""
    cfg = cfg_for("dual", quant_group_size=32)
    groups = BLOCK // 32
    assert kernels_in(lambda x: ash_compress.compress_blocks_pallas(x, cfg),
                      sds((BLOCKS, BLOCK), jnp.float32)) == 1
    assert kernels_in(
        lambda q, s, a: ash_decompress.decompress_blocks_pallas(q, s, a, cfg),
        sds((BLOCKS, BLOCK), cfg.format_spec.dtype),
        sds((BLOCKS, groups), jnp.float32), sds((BLOCKS,), jnp.float32)) == 1


def test_butterfly_kernel_compiles(sds):
    """Off the training path, kept as the VPU counterpoint to the MXU
    rotation: its lane-rotation butterfly must compile too."""
    cfg = cfg_for("dual")
    assert kernels_in(lambda x: compress_blocks_butterfly(x, cfg),
                      sds((BLOCKS, BLOCK), jnp.float32)) == 1
