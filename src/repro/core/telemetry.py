"""Shared observability layer for the trainer and the serving engine.

One reporter abstraction feeds both consumers (the ROADMAP's adaptive
compression controller wants a single stats stream to train its policy
on):

  * the TRAINER logs :func:`comm_metrics` — the static per-path wire
    accounting of the plan that actually ran a step — on its log steps
    (``comm/*`` keys);
  * the SERVING ENGINE emits per-request latency rows (``serve/request``
    events: queue wait, prefill time, per-token decode time, achieved
    wire bytes) and engine counters through a :class:`Reporter`.

The training loop's host phases are profiler spans (:class:`StepPhases`,
names below), and :class:`StepProfile` captures a profiler trace of a
few steps plus the compiled step's HLO text, whose ``op_name`` metadata
carries the step's named scopes (``taco/*``, ``attn``, ``attn/core``,
``mlp``, ``head``, ``optim``) for the device ops of the trace.

Everything here is host-side Python on static plan data — the only
device work is the one cached probe encode behind
:func:`achieved_probe_ratio`.
"""
from __future__ import annotations

import collections
import contextlib
import logging
import os
import time

import jax

# --------------------------------------------------------------------------
# training-loop spans (jax.profiler TraceMe names; always on: with no
# profiler running a span records nothing)
# --------------------------------------------------------------------------

#: One loop iteration, a step marker carrying ``step_num``.
STEP_SPAN = "train"
#: The data source's ``batch(step)``: host rows for the step.
SPAN_DATA = "train/data"
#: ``place``: the rows' ``device_put`` onto the mesh.
SPAN_PLACE = "train/place"
#: Plan resolution and the compiled step's enqueue; returns before the
#: device finishes.
SPAN_DISPATCH = "train/dispatch"
#: The blocking read of the step's loss: the host waits for the device.
SPAN_SYNC = "train/sync"
#: Straggler watchdog, telemetry and the log line.
SPAN_LOG = "train/log"
#: Checkpoint save.
SPAN_CKPT = "train/ckpt"


# --------------------------------------------------------------------------
# named scopes of the compiled step (jax.named_scope: op_name metadata only,
# the compiled program is otherwise unchanged); rematerialised scopes appear
# again under the backward pass's prefix
# --------------------------------------------------------------------------

#: A compressed hop's encode into its wire buffer (``_transport``).
SCOPE_ENCODE = "taco/encode"
#: The hop's one lax collective on the wire buffer.
SCOPE_MOVE = "taco/move"
#: The decode (or fused decode-and-sum) of the moved wire buffer.
SCOPE_DECODE = "taco/decode"
#: ``pack_wire``/``unpack_wire``: the components' byte relayout.
SCOPE_WIRE = "taco/wire"
#: Attention: projections, core and output projection.
SCOPE_ATTN = "attn"
#: The attention core inside ``attn``: the chunked softmax, forward and
#: recompute backward.
SCOPE_ATTN_CORE = SCOPE_ATTN + "/core"
#: The feed-forward block.
SCOPE_MLP = "mlp"
#: Embedding, final norm, output head and loss.
SCOPE_HEAD = "head"
#: Gradient finalisation and the AdamW update.
SCOPE_OPTIM = "optim"
STEP_SCOPES = (SCOPE_ENCODE, SCOPE_MOVE, SCOPE_DECODE, SCOPE_WIRE,
               SCOPE_ATTN, SCOPE_ATTN_CORE, SCOPE_MLP, SCOPE_HEAD,
               SCOPE_OPTIM)


class StepPhases:
    """Host phases of one training step: each is a profiler span and a
    ``time.perf_counter`` duration, kept until the next step begins."""

    def __init__(self):
        self.ms: dict[str, float] = {}
        self._open: tuple[str, float] | None = None

    def step(self, step: int):
        """The iteration's step span; clears the previous step's split."""
        self.ms = {}
        return jax.profiler.StepTraceAnnotation(STEP_SPAN, step_num=step)

    @contextlib.contextmanager
    def phase(self, name: str):
        short = name.rpartition("/")[2]
        t0 = time.perf_counter()
        self._open = (short, t0)
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self._open = None
            self.ms[short] = (time.perf_counter() - t0) * 1e3

    def split(self) -> dict[str, float]:
        """Milliseconds per phase of this step so far, the open phase's
        up to now."""
        out = dict(self.ms)
        if self._open is not None:
            short, t0 = self._open
            out[short] = (time.perf_counter() - t0) * 1e3
        return out


class StepProfile:
    """A profiler trace of steps ``[first, first + count)`` written to
    ``out_dir``, and beside it ``step.hlo.txt``: the compiled step's HLO
    text, whose ``op_name`` metadata names the scope of every device op
    in the trace."""

    HLO_FILE = "step.hlo.txt"

    def __init__(self, out_dir: str, first: int, count: int):
        self.out_dir, self.first, self.count = out_dir, first, count
        self.active = self.done = False

    def before(self, step: int) -> None:
        if not (self.active or self.done) \
                and self.first <= step < self.first + self.count:
            jax.profiler.start_trace(self.out_dir)
            self.active = True

    def after(self, next_step: int, step_hlo) -> None:
        """Stop once the last profiled step has run; ``step_hlo()``
        returns the compiled step's HLO text."""
        if self.active and next_step >= self.first + self.count:
            self.stop()
            with open(os.path.join(self.out_dir, self.HLO_FILE), "w") as f:
                f.write(step_hlo())

    def stop(self) -> None:
        if self.active:
            jax.profiler.stop_trace()
            self.active, self.done = False, True


# --------------------------------------------------------------------------
# plan-level wire accounting (the trainer's comm/* block)
# --------------------------------------------------------------------------

_PROBE_RATIO_CACHE: dict = {}


def achieved_probe_ratio(codec) -> float:
    """Achieved/slot byte fraction of ``codec`` on an all-zero probe slot
    — the near-zero-payload FLOOR of its variable wire layout (what the
    achieved telemetry converges to as padding dominates a batch).  Runs
    one encode on device, so results are cached per codec; only
    meaningful for variable layouts (callers gate on
    ``CommPlan.wire_variable``)."""
    from repro.core import collectives as cc
    key = cc._slot_key(codec)  # negotiated variants share the cache entry
    cached = _PROBE_RATIO_CACHE.get(key)
    if cached is None:
        import jax.numpy as jnp

        n = 4 * key.granule
        probe = jnp.zeros((1, n), jnp.bfloat16)
        ach = cc.achieved_slot_bytes(key, probe)
        slot = cc.wire_slot_bytes(key, n)
        cached = float(ach[0]) / float(slot)
        _PROBE_RATIO_CACHE[key] = cached
    return cached


def clear_probe_cache() -> None:
    """Drop every cached :func:`achieved_probe_ratio` entry.  Tests that
    register throwaway codec variants call this (tests/conftest.py,
    autouse) so a stale probe ratio can never leak across tests; prod
    consumers never need it — the cache is keyed by frozen codec
    identity and a codec's floor never changes."""
    _PROBE_RATIO_CACHE.clear()


def comm_metrics(plan, *, spec: str | None = None,
                 warmup_active: bool | None = None) -> dict:
    """Per-path wire telemetry for the plan that ran (static — no device
    work beyond the cached variable-layout probe).  Key set is shared by
    the trainer's step metrics and the serving engine's run summary."""
    m: dict = {}
    if spec is not None:
        m["comm/spec"] = spec
    if warmup_active is not None:
        m["comm/warmup_active"] = 1.0 if warmup_active else 0.0
    for path, bpe in plan.wire_bytes_per_element().items():
        m[f"comm/{path}_bytes_per_elem"] = bpe
    for path, nc in plan.wire_chunks().items():
        if nc != 1:   # chunked ring transport active on path
            m[f"comm/{path}_chunks"] = nc
    for path, var in plan.wire_variable().items():
        if var:   # bounded-but-ragged wire layout on path: bytes_per_elem
            # above is the slot BOUND; surface the flag plus the
            # all-zero achieved floor (cached — one probe per codec)
            m[f"comm/{path}_wire_variable"] = 1.0
            m[f"comm/{path}_achieved_floor_ratio"] = \
                achieved_probe_ratio(getattr(plan, path))
    for path, mode in plan.slot_modes().items():
        if mode == "auto":   # controller-renegotiated slot on path:
            # surface the flag plus the bytes/elem the NEGOTIATED bound
            # moves (equals the slot bound while the controller is
            # bootstrapping or resyncing, i.e. moved_frac is unset)
            codec = getattr(plan, path)
            frac = getattr(codec, "moved_frac", None)
            # moved_frac is a per-chunk tuple when the SlotController
            # negotiated it, but tolerate a bare scalar (or None) —
            # hand-built codecs and future controllers need not tuple-ize
            if frac is None:
                worst = 1.0
            elif isinstance(frac, (int, float)):
                worst = float(frac)
            else:
                worst = max(frac)
            m[f"comm/{path}_slot_auto"] = 1.0
            m[f"comm/{path}_negotiated_bytes"] = \
                m[f"comm/{path}_bytes_per_elem"] * worst
    for path, esc in plan.escalation_modes().items():
        if esc is not None:   # escalate= policy on path: surface the
            # static threshold; the live error EMA / escalated flag come
            # from the ErrorEscalationController's metrics() (merged into
            # the same comm/* family by the trainer and serve engine)
            m[f"comm/{path}_escalate_threshold"] = float(esc[1])
    return m


# --------------------------------------------------------------------------
# event reporter (the serving engine's per-request stream)
# --------------------------------------------------------------------------

class Reporter:
    """Append-only event/counter sink.

    ``event(kind, **fields)`` records one row; rows are plain dicts so
    consumers (launch CLIs, benchmarks, the policy engine's controllers)
    aggregate without schema machinery.  An optional logger mirrors each
    event at DEBUG and counters at the caller's discretion.

    ``maxlen`` turns the row store into a ring buffer keeping only the
    newest ``maxlen`` rows — long serving runs emit one row per request
    and would otherwise grow without bound (the serve engine passes
    this).  Counters are cumulative either way, and ``drain()`` still
    returns whatever rows are currently held and empties the store."""

    def __init__(self, log: logging.Logger | None = None, *,
                 maxlen: int | None = None):
        if maxlen is not None and maxlen < 1:
            raise ValueError(f"Reporter maxlen must be >= 1, got {maxlen}")
        self.rows = [] if maxlen is None \
            else collections.deque(maxlen=maxlen)
        self.counters: dict[str, float] = {}
        self._log = log

    @property
    def maxlen(self) -> int | None:
        return getattr(self.rows, "maxlen", None)

    def event(self, kind: str, **fields) -> dict:
        row = {"kind": kind, "t": time.monotonic(), **fields}
        self.rows.append(row)
        if self._log is not None:
            self._log.debug("%s %s", kind, fields)
        return row

    def count(self, name: str, n: float = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n

    def of_kind(self, kind: str) -> list[dict]:
        return [r for r in self.rows if r["kind"] == kind]

    def drain(self) -> list[dict]:
        rows = list(self.rows)
        self.rows.clear()
        return rows


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in [0,100]) of a non-empty sequence."""
    import math
    values = list(values)
    if not values:                 # before sorting: the emptiness of a
        # one-shot iterable must be judged on the materialized values,
        # and an empty input should not pay (or mask) the sort
        raise ValueError("percentile of empty sequence")
    xs = sorted(values)
    rank = max(1, math.ceil(len(xs) * q / 100.0))
    return float(xs[min(rank, len(xs)) - 1])
