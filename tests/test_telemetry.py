"""Telemetry unit tests: comm_metrics key families and robustness,
percentile edge cases, the Reporter ring-buffer mode, and the probe-ratio
cache hygiene hook.

``comm_metrics`` is the shared key contract between the trainer's step
metrics and the serving engine's run summary, so the families are pinned
here: ``comm/<path>_bytes_per_elem`` always; ``_chunks`` only when a
ring transport is active; ``_wire_variable``/``_achieved_floor_ratio``
for ragged layouts; ``_slot_auto``/``_negotiated_bytes`` under slot
renegotiation; ``_escalate_threshold`` under an escalate= policy.
"""
import dataclasses

import pytest

from repro.core import telemetry
from repro.core.registry import from_spec


# --------------------------------------------------------------------------
# comm_metrics key families
# --------------------------------------------------------------------------

def test_comm_metrics_baseline_keys():
    m = telemetry.comm_metrics(from_spec("baseline"), spec="baseline",
                               warmup_active=False)
    assert m["comm/spec"] == "baseline"
    assert m["comm/warmup_active"] == 0.0
    assert m["comm/tp_fwd_bytes_per_elem"] == 2.0      # bf16 wire
    # no chunked/ragged/negotiated/escalating path -> no optional keys
    assert not any(k.endswith(("_chunks", "_wire_variable", "_slot_auto",
                               "_escalate_threshold")) for k in m)


def test_comm_metrics_optional_families():
    plan = from_spec("tp_fwd=taco+zle:jnp:slot=auto:chunks=4,"
                     "grad_rs=int8:escalate=bf16@0.1")
    m = telemetry.comm_metrics(plan)
    assert m["comm/tp_fwd_chunks"] == 4
    assert m["comm/tp_fwd_wire_variable"] == 1.0
    assert 0.0 < m["comm/tp_fwd_achieved_floor_ratio"] < 1.0
    assert m["comm/tp_fwd_slot_auto"] == 1.0
    assert m["comm/grad_rs_escalate_threshold"] == 0.1
    # the bound moves in full while moved_frac is unset (bootstrapping)
    assert m["comm/tp_fwd_negotiated_bytes"] == \
        m["comm/tp_fwd_bytes_per_elem"]


def test_comm_metrics_negotiated_bytes_uses_worst_chunk():
    plan = from_spec("tp_fwd=taco+zle:jnp:slot=auto:chunks=2")
    neg = dataclasses.replace(plan.tp_fwd, moved_frac=(0.25, 0.5))
    m = telemetry.comm_metrics(dataclasses.replace(plan, tp_fwd=neg))
    assert m["comm/tp_fwd_negotiated_bytes"] == \
        pytest.approx(m["comm/tp_fwd_bytes_per_elem"] * 0.5)


class _FakeCodec:
    """Duck-typed negotiated codec: hand-built controllers may carry a
    bare scalar (or None) moved_frac instead of the per-chunk tuple."""

    def __init__(self, moved_frac):
        self.moved_frac = moved_frac


class _FakePlan:
    """One-path plan exposing exactly the accessor surface comm_metrics
    reads."""

    def __init__(self, codec):
        self.tp_fwd = codec

    def wire_bytes_per_element(self):
        return {"tp_fwd": 1.0}

    def wire_chunks(self):
        return {"tp_fwd": 1}

    def wire_variable(self):
        return {"tp_fwd": False}

    def slot_modes(self):
        return {"tp_fwd": "auto"}

    def escalation_modes(self):
        return {"tp_fwd": None}


@pytest.mark.parametrize("frac,worst", [
    (None, 1.0),           # unset: the full bound moves
    (0.5, 0.5),            # bare scalar tolerated
    (0.25, 0.25),
    ((0.125, 0.75), 0.75),  # per-chunk tuple: worst chunk governs
])
def test_comm_metrics_tolerates_scalar_moved_frac(frac, worst):
    m = telemetry.comm_metrics(_FakePlan(_FakeCodec(frac)))
    assert m["comm/tp_fwd_negotiated_bytes"] == pytest.approx(worst)


# --------------------------------------------------------------------------
# percentile
# --------------------------------------------------------------------------

def test_percentile_nearest_rank_values():
    xs = [15, 20, 35, 40, 50]
    assert telemetry.percentile(xs, 5) == 15
    assert telemetry.percentile(xs, 30) == 20
    assert telemetry.percentile(xs, 40) == 20
    assert telemetry.percentile(xs, 50) == 35
    assert telemetry.percentile(xs, 100) == 50
    assert telemetry.percentile(iter(xs), 50) == 35    # one-shot iterable


def test_percentile_empty_raises():
    with pytest.raises(ValueError):
        telemetry.percentile([], 50)
    # an EMPTY one-shot iterable must raise too (the emptiness check
    # runs on the materialized values, before the sort)
    with pytest.raises(ValueError):
        telemetry.percentile(iter(()), 99)


# --------------------------------------------------------------------------
# Reporter ring-buffer mode
# --------------------------------------------------------------------------

def test_reporter_unbounded_by_default():
    rep = telemetry.Reporter()
    assert rep.maxlen is None
    for i in range(100):
        rep.event("k", i=i)
    assert len(rep.rows) == 100


def test_reporter_maxlen_keeps_newest_rows():
    rep = telemetry.Reporter(maxlen=4)
    assert rep.maxlen == 4
    for i in range(10):
        rep.event("k", i=i)
        rep.count("events")
    assert [r["i"] for r in rep.rows] == [6, 7, 8, 9]
    # counters are cumulative regardless of evicted rows
    assert rep.counters["events"] == 10
    assert [r["i"] for r in rep.of_kind("k")] == [6, 7, 8, 9]


def test_reporter_maxlen_drain_semantics():
    rep = telemetry.Reporter(maxlen=3)
    for i in range(5):
        rep.event("k", i=i)
    drained = rep.drain()
    assert [r["i"] for r in drained] == [2, 3, 4]
    assert len(rep.rows) == 0            # drain empties the ring
    rep.event("k", i=99)                 # ...and it keeps working after
    assert [r["i"] for r in rep.rows] == [99]


@pytest.mark.parametrize("bad", [0, -1, -100])
def test_reporter_rejects_nonpositive_maxlen(bad):
    with pytest.raises(ValueError):
        telemetry.Reporter(maxlen=bad)


# --------------------------------------------------------------------------
# probe-ratio cache hygiene
# --------------------------------------------------------------------------

def test_clear_probe_cache():
    from repro.core.registry import codec_from_spec
    codec = codec_from_spec("taco+zle:jnp")
    ratio = telemetry.achieved_probe_ratio(codec)
    assert 0.0 < ratio < 1.0
    assert telemetry._PROBE_RATIO_CACHE          # populated by the call
    telemetry.clear_probe_cache()
    assert not telemetry._PROBE_RATIO_CACHE
    # recompute lands on the same value (the floor is deterministic)
    assert telemetry.achieved_probe_ratio(codec) == ratio


# --------------------------------------------------------------------------
# the training loop's phase split
# --------------------------------------------------------------------------

@pytest.mark.parametrize("slow_phase", ["sync", "data"])
def test_straggler_warning_prints_the_phase_split(caplog, slow_phase):
    """The watchdog's straggler warning carries the step's host split,
    in loop order, the open phase timed up to the warning."""
    import logging
    import time

    from repro.runtime.fault_tolerance import StepWatchdog

    wd = StepWatchdog()
    for _ in range(5):
        assert not wd.observe(0.01, {"sync": 10.0})
    ph = telemetry.StepPhases()
    with ph.step(7):
        for name in ("train/data", "train/place", "train/dispatch",
                     "train/sync"):
            with ph.phase(name):
                if name.endswith(slow_phase):
                    time.sleep(0.05)
        with ph.phase(telemetry.SPAN_LOG):
            split = ph.split()
            with caplog.at_level(logging.WARNING, logger="repro.ft"):
                assert wd.observe(0.05, split)
    assert list(split) == ["data", "place", "dispatch", "sync", "log"]
    assert split[slow_phase] >= 50.0
    assert ph.ms["log"] >= split["log"]
    msg, = [r.getMessage() for r in caplog.records]
    assert msg.startswith("straggler step: 0.050s vs median 0.010s (data ")
    assert f"{slow_phase} {split[slow_phase]:.1f}" in msg
    assert msg.endswith(f"log {split['log']:.1f})")
