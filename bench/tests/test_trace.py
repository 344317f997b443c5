"""The reduction from a profiler trace to the per-layer metrics, on a small
trace recorded on four TPU v5e chips (``record_trace.py``): a shard_map
running the program's compressed all-gather and reduce-scatter (``taco``)
around a matrix product and a native all-reduce, three times, with the
compiled module's HLO text for the kernel names.

Every number pinned here was read once from that trace by this code; the
test keeps the reduction from changing what it reads.  The device clock
runs about a millisecond ahead of the host spans in this trace, so the
window holds two of the three steps' kernels on each chip."""
import gzip
import os

import pytest

import xtrace
from harness import LayerRun
from metrics import (codec_kernel_ms, collective_ms, device_idle_frac,
                     exposed_collective_ms)

DATA = os.path.join(os.path.dirname(__file__), "data", "small_trace")


@pytest.fixture(scope="module")
def trace():
    with gzip.open(os.path.join(DATA, "small.hlo.txt.gz"), "rt") as f:
        kernels = xtrace.kernel_symbols(f.read())
    with gzip.open(os.path.join(DATA, "small.xplane.pb.gz"), "rb") as f:
        return xtrace.load(f.read(), kernels), kernels


def layer_run(tr):
    return LayerRun(trace=tr, tokens_per_s=None, flops_per_token=0.0,
                    arch={}, wire=None, tp=4, remat="full", seq=0, batch=0,
                    chips=4, peaks={})


def test_kernel_names_come_from_the_compiled_module(trace):
    _, kernels = trace
    found = sorted({codec_kernel_ms.kernel_of(v) for v in kernels.values()}
                   - {None})
    assert found == PINNED["kernels"]


def test_window_steps_and_devices(trace):
    tr, _ = trace
    assert sorted(tr.ops) == [0, 1, 2, 3]
    assert tr.steps == 3
    assert tr.window[1] - tr.window[0] == PINNED["window_ns"]


def test_codec_kernel_calls_and_time(trace):
    tr, _ = trace
    per = codec_kernel_ms.per_kernel_ns(tr)
    assert {k: n for k, (_, n) in per.items()} == PINNED["calls"]
    assert codec_kernel_ms.read(layer_run(tr)) == pytest.approx(
        PINNED["codec_kernel_ms"], rel=1e-12)


def test_collectives_and_idle(trace):
    tr, _ = trace
    lr = layer_run(tr)
    assert collective_ms.read(lr) == pytest.approx(
        PINNED["collective_ms"], rel=1e-12)
    assert exposed_collective_ms.read(lr) == pytest.approx(
        PINNED["exposed_collective_ms"], rel=1e-12)
    assert device_idle_frac.read(lr) == pytest.approx(
        PINNED["device_idle_frac"], rel=1e-12)


def test_interval_arithmetic():
    a = xtrace.union([(0, 10), (5, 20), (30, 40)])
    assert a == [[0, 20], [30, 40]]
    assert xtrace.subtract(a, xtrace.union([(2, 3), (15, 35)])) == \
        [[0, 2], [3, 15], [35, 40]]
    assert xtrace.length(a) == 30


def test_op_names_parse():
    assert xtrace.parse_op(
        '%f.3 = (f8e4m3fn[8,256]{1,0}, f32[8,1]{1,0}) custom-call(bf16[8,256]'
        '{1,0} %x), custom_call_target="tpu_custom_call"') == \
        ("%f.3", "custom-call")
    assert xtrace.is_collective("%all-gather-start.2 all-gather-start")
    assert xtrace.is_collective("%reduce-scatter.1 reduce-scatter")
    assert not xtrace.is_collective("%fusion.4 fusion")


# read from the trace by this code; its three steps are short
# (about 2 ms of device work each, between host spans), so most of its
# window is idle
PINNED = {
    "kernels": ["_compress_kernel", "_decompress_kernel",
                "_decompress_reduce_kernel"],
    "window_ns": 5730770,
    "calls": {"_compress_kernel": 16, "_decompress_kernel": 8,
              "_decompress_reduce_kernel": 12},
    "codec_kernel_ms": 0.15481216666666667,
    "collective_ms": 0.07121899999999999,
    "exposed_collective_ms": 0.07121899999999999,
    "device_idle_frac": 75.67166366823305,
}
