"""The program's training-loop spans, and each chip's clock moved onto the
host's, from the run's profiler trace.

A per-layer reader is given ``xtrace.Trace``: the device ops and the
benchmark's own host spans.  The program's spans (a ``train`` step span
per loop iteration with its ``step_num``, and the ``train/*`` phases
inside it) and the events that tie each chip's clock to the host's are
read here, from the newest ``*.xplane.pb`` under ``.bench_trace/``, where
the harness leaves the trace until every reader has run.  A trace of a
program without those spans gives no steps, and the readers no number.

The clock offset.  Each program run on a chip is an ``XLA Modules`` event
on its ``/device:TPU:N`` plane; the host's ``DoEnqueueProgram`` and
``CompleteCallbacks`` events of the same run carry ``device_ordinal`` N
and the same ``run_id`` (run ids are counted per chip, so both are
needed).  A run starts on the device after the host enqueued it and ends
before the host's completion callback starts, so the offset that moves
device time onto host time lies in ``[max(enqueue - module start),
min(completion - module end)]`` over the chip's runs.  The lower bound
is used; a chip whose bounds cross has no offset.
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import os

import cells
import xtrace

TRACE_ROOT = cells.ROOT / ".bench_trace"
STEP = "train"
PHASE_PREFIX = "train/"
SYNC = "sync"
MODULES_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"
COMPLETE = "CompleteCallbacks"


@dataclasses.dataclass
class Loop:
    steps: list      # [(start_ns, end_ns, step_num)] complete step spans
    phases: list     # [(start_ns, end_ns, phase)] inside those steps
    offsets: dict    # device id -> (lower_ns, upper_ns) clock offset bounds

    def offset(self, dev) -> int | None:
        """Nanoseconds to add to the device's times; None where the
        chip's bounds cross or it has no runs."""
        lo, hi = self.offsets.get(dev, (None, None))
        return lo if lo is not None and lo <= hi else None


def newest_trace(root=None) -> str | None:
    found = glob.glob(f"{root or TRACE_ROOT}/**/*.xplane.pb", recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def for_run(run) -> Loop | None:
    """The loop spans of the run's trace, or None without a trace."""
    if run.trace is None:
        return None
    path = newest_trace()
    if path is None:
        return None
    st = os.stat(path)
    return _load_file(path, st.st_mtime_ns, st.st_size)


@functools.lru_cache(maxsize=2)
def _load_file(path, mtime_ns, size) -> Loop:
    return load(path)


def load(src) -> Loop:
    """Read a trace file (path) or its serialized bytes."""
    from jax.profiler import ProfileData
    data = ProfileData.from_serialized_xspace(src) \
        if isinstance(src, bytes) else ProfileData.from_file(src)
    steps, phases, modules, enqueue, complete = [], [], {}, {}, {}
    for plane in data.planes:
        m = xtrace.DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name != MODULES_LINE:
                    continue
                for ev in line.events:
                    run_id = dict(ev.stats).get("run_id")
                    modules[(dev, run_id)] = (
                        int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if name == STEP:
                        steps.append((int(ev.start_ns),
                                      int(ev.start_ns + ev.duration_ns),
                                      int(dict(ev.stats)["step_num"])))
                    elif name.startswith(PHASE_PREFIX):
                        phases.append((int(ev.start_ns),
                                       int(ev.start_ns + ev.duration_ns),
                                       name[len(PHASE_PREFIX):]))
                    elif name in (ENQUEUE, COMPLETE):
                        st = dict(ev.stats)
                        key = (st.get("device_ordinal"), st.get("run_id"))
                        into = enqueue if name == ENQUEUE else complete
                        t = int(ev.start_ns)   # the earliest of a run's
                        into[key] = min(into.get(key, t), t)
    steps.sort()
    inside = [(s, e, n) for s, e, n in sorted(phases)
              if any(lo <= s and e <= hi for lo, hi, _ in steps)]
    return Loop(steps=steps, phases=inside,
                offsets=offset_bounds(modules, enqueue, complete))


def offset_bounds(modules, enqueue, complete) -> dict:
    """``device -> (lower, upper)`` bounds of the offset in nanoseconds
    from ``(device, run_id) -> (start, end)`` module runs and the host's
    enqueue and completion starts under the same keys."""
    out = {}
    for key, (s, e) in modules.items():
        if key not in enqueue or key not in complete:
            continue
        lo, hi = out.get(key[0], (None, None))
        a, b = enqueue[key] - s, complete[key] - e
        out[key[0]] = (a if lo is None else max(lo, a),
                       b if hi is None else min(hi, b))
    return out


def host_loop_ns(loop: Loop) -> int:
    """Host time inside the step spans and outside ``train/sync``."""
    sync = sum(e - s for s, e, n in loop.phases if n == SYNC)
    return sum(e - s for s, e, _ in loop.steps) - sync


def idle_split(tr: xtrace.Trace, loop: Loop, dev) -> dict | None:
    """Nanoseconds of the device's idle time, on the host's clock, from
    the first step span's start to the last one's end, by what the host
    was doing: a phase's name, ``step`` inside a step span but in no
    phase, ``between`` outside the step spans.  None without steps or
    without an offset for the chip."""
    delta = loop.offset(dev)
    if not loop.steps or delta is None:
        return None
    window = [[loop.steps[0][0], loop.steps[-1][1]]]
    busy = xtrace.union((s + delta, e + delta)
                        for s, e, _, _ in tr.ops.get(dev, ()))
    idle = xtrace.subtract(window, busy)
    out = {}
    for s, e, name in loop.phases:
        out[name] = out.get(name, 0) + _overlap(idle, s, e)
    in_steps = sum(_overlap(idle, s, e) for s, e, _ in loop.steps)
    out["step"] = in_steps - sum(out.values())
    out["between"] = xtrace.length(idle) - in_steps
    return out


def _overlap(merged, lo, hi) -> int:
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in merged)
