"""The program's loop spans and each chip's clock offset, read from two
traces recorded on four TPU v5e chips:

* ``small_trace`` (``record_trace.py``): a shard_map run three times, no
  program spans, as a program without them (the parent of the spans)
  leaves a traced run;
* ``loop_trace`` (``record_loop_trace.py``): the test cell
  ``tiny.tp4.taco`` through the harness's traced path, with the
  program's ``train`` step spans and ``train/*`` phases.
"""
import gzip
import os
import shutil

import pytest

import loopspans
import xtrace
from harness import LayerRun
from metrics import host_bound_idle_ms, host_loop_ms

DATA = os.path.join(os.path.dirname(__file__), "data")
PHASES = ["data", "place", "dispatch", "sync", "log"]


def _bytes(name):
    with gzip.open(os.path.join(DATA, name), "rb") as f:
        return f.read()


def layer_run(tr):
    return LayerRun(trace=tr, tokens_per_s=None, flops_per_token=0.0,
                    arch={}, wire=None, tp=4, remat="full", seq=0, batch=0,
                    chips=4, peaks={})


@pytest.fixture(scope="module")
def small():
    raw = _bytes("small_trace/small.xplane.pb.gz")
    return raw, xtrace.load(raw), loopspans.load(raw)


@pytest.fixture(scope="module")
def loop():
    raw = _bytes("loop_trace/loop.xplane.pb.gz")
    return raw, xtrace.load(raw), loopspans.load(raw)


def _pairs_by_correlation(raw):
    """``device -> {run_id}`` paired by the profiler's own correlation
    ids (a module's ``_c`` is its enqueue's ``_p``), independent of
    ``device_ordinal``."""
    from jax.profiler import ProfileData
    mods, enq = {}, {}
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        m = xtrace.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            for ev in line.events:
                if m and line.name == loopspans.MODULES_LINE:
                    st = dict(ev.stats)
                    mods[st["_c"]] = (int(m.group(1)), st["run_id"])
                elif ev.name == loopspans.ENQUEUE:
                    st = dict(ev.stats)
                    enq[st["_p"]] = (st["device_ordinal"], st["run_id"])
    assert mods and set(mods) <= set(enq)
    return mods, enq


@pytest.mark.parametrize("which", ["small", "loop"])
def test_device_ordinal_is_the_plane_index(which, request):
    """The key the offset pairs on, ``(device_ordinal, run_id)``, names
    the same program run as the correlation ids do."""
    raw = request.getfixturevalue(which)[0]
    mods, enq = _pairs_by_correlation(raw)
    assert all(mods[c] == enq[c] for c in mods)


def test_offset_bounds_on_the_small_trace(small):
    """Per chip the device clock sits 1.33-1.37 ms (lower bound, the
    latest enqueue relative to its module's start) to 1.84-1.86 ms (upper
    bound) behind the host's."""
    _, tr, lp = small
    assert sorted(lp.offsets) == sorted(tr.ops) == [0, 1, 2, 3]
    for dev, (lo, hi) in lp.offsets.items():
        assert 1.31e6 <= lo <= hi <= 1.86e6, (dev, lo, hi)
        assert lp.offset(dev) == lo


def test_a_program_without_loop_spans_reads_nothing(small, tmp_path,
                                                    monkeypatch):
    raw, tr, lp = small
    assert lp.steps == [] and lp.phases == []
    path = tmp_path / "run" / "host.xplane.pb"
    path.parent.mkdir()
    path.write_bytes(raw)
    monkeypatch.setattr(loopspans, "TRACE_ROOT", tmp_path)
    lr = layer_run(tr)
    assert host_loop_ms.read(lr) is None
    assert host_bound_idle_ms.read(lr) is None


def test_loop_spans_hold_the_phases_in_order(loop):
    _, _, lp = loop
    nums = [n for _, _, n in lp.steps]
    assert len(nums) >= 3 and nums == list(range(nums[0], nums[0] + len(nums)))
    for lo, hi, n in lp.steps:
        inside = [p for s, e, p in lp.phases if lo <= s and e <= hi]
        assert inside == PHASES, n


def test_idle_split_adds_up_to_the_whole_idle_time(loop):
    """On every chip the idle time from the first step span's start to
    the last one's end, split by what the host was doing, adds up to the
    whole, and the host-bound part is at most the idle time per step."""
    _, tr, lp = loop
    assert sorted(lp.offsets) == sorted(tr.ops) == [0, 1, 2, 3]
    per_step_idle = []
    for dev in tr.ops:
        lo, hi = lp.offsets[dev]
        assert lo <= hi, dev
        split = loopspans.idle_split(tr, lp, dev)
        busy = xtrace.union((s + lo, e + lo) for s, e, _, _ in tr.ops[dev])
        idle = xtrace.length(xtrace.subtract(
            [[lp.steps[0][0], lp.steps[-1][1]]], busy))
        assert all(v >= 0 for v in split.values()), split
        assert sum(split.values()) == idle, dev
        per_step_idle.append(idle / len(lp.steps) * 1e-6)
    lr = layer_run(tr)
    bound = host_bound_idle_ms.read_from(lr, lp)
    assert 0 < bound <= sum(per_step_idle) / len(per_step_idle)


def test_readers_find_the_newest_trace(loop, tmp_path, monkeypatch):
    """Read once from the loop trace by this code (steps 447-449; each
    chip's offset 0.35-0.37 ms, bounded above by 0.80-0.84 ms): the
    readers find the trace where the harness leaves it."""
    raw, tr, lp = loop
    for lo, hi in lp.offsets.values():
        assert 0.35e6 <= lo <= 0.37e6 and 0.80e6 <= hi <= 0.84e6
    path = tmp_path / "cell" / "plugins" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(raw)
    monkeypatch.setattr(loopspans, "TRACE_ROOT", tmp_path)
    lr = layer_run(tr)
    assert host_loop_ms.read(lr) == pytest.approx(4.817363333333334,
                                                  rel=1e-12)
    assert host_bound_idle_ms.read(lr) == pytest.approx(4.206171833333333,
                                                        rel=1e-12)
    shutil.rmtree(tmp_path / "cell")
    assert loopspans.newest_trace(tmp_path) is None


def test_the_chip_compiled_step_carries_the_program_scopes():
    """The test cell's step as the TPU compiler emitted it keeps each of
    the program's named scopes in some op's ``op_name``."""
    import re
    with gzip.open(os.path.join(DATA, "loop_trace", "loop.hlo.txt.gz"),
                   "rt") as f:
        names = set(re.findall(r'op_name="([^"]*)"', f.read()))
    for scope in ("taco/encode", "taco/move", "taco/decode", "taco/wire",
                  "attn", "mlp", "head", "optim"):
        pat = re.compile(r"(^|[/(])" + re.escape(scope) + r"([/)]|$)")
        assert any(pat.search(n) for n in names), scope
