"""From a profiler trace (``.xplane.pb``) to the intervals the per-layer
metrics read.

A ``Trace`` holds, for each TPU device plane, the operations of its
``XLA Ops`` line as ``(start_ns, end_ns, name, label)`` in time order,
the asynchronous ones of its ``Async XLA Ops`` line (a transfer from its
start to its done), and the benchmark's own host spans (``bench/data``, ``bench/trainer``)
from the host plane.  The traced window runs from the start of the first
``bench/data`` span to the start of the last one: whole iterations of the
trainer's loop.

On the TPU an op's event is named by its HLO instruction text
(``%f.3 = (...) custom-call(...), custom_call_target=...``).  ``name`` is
the instruction's name and opcode (``%f.3 custom-call``).  A Pallas
kernel's own name is not in the trace: it lives in the custom call's
serialized Mosaic body in the compiled module.  ``kernel_symbols`` reads
it from the module's HLO text, and ``label`` is ``name`` followed by the
symbols of that instruction, so that a reader can match a kernel by the
name of its Pallas kernel function.
"""
from __future__ import annotations

import base64
import dataclasses
import json
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
SPAN_PREFIX = "bench/"
COLLECTIVE = re.compile(
    r"^(all-gather|reduce-scatter|all-reduce|all-to-all|collective-permute"
    r"|ragged-all-to-all)(-start|-done)?$")
SYMBOL = re.compile(rb"[A-Za-z_][A-Za-z0-9_]{2,}")


@dataclasses.dataclass
class Trace:
    ops: dict          # device id -> [(start_ns, end_ns, name, label)]
    async_ops: dict    # device id -> the same, for asynchronous ops
    spans: list        # [(start_ns, end_ns, name)] host spans, bench/ stripped
    window: tuple      # (start_ns, end_ns)
    steps: int         # loop iterations inside the window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def clipped(self, dev, line: str = OPS_LINE):
        """The device's ops (or asynchronous ops) clipped to the window."""
        lo, hi = self.window
        src = self.ops if line == OPS_LINE else self.async_ops
        for s, e, name, label in src.get(dev, ()):
            s, e = max(s, lo), min(e, hi)
            if e > s:
                yield s, e, name, label


def parse_op(text: str) -> tuple[str, str]:
    """(instruction name, opcode) of an HLO instruction's text."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text, ""
    name = head.split()[-1]
    if rest.startswith("("):          # tuple shape: skip to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    return name, rest.lstrip().partition("(")[0]


def kernel_symbols(hlo_text: str) -> dict[str, str]:
    """``%instruction -> symbols`` of every TPU custom call in a compiled
    module's HLO text, read from its serialized kernel body."""
    out = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name, _ = parse_op(line.strip())
        i = line.find("backend_config=")
        if i < 0:
            continue
        try:
            cfg = json.JSONDecoder().raw_decode(
                line[i + len("backend_config="):])[0]
            body = base64.b64decode(cfg["custom_call_config"]["body"])
        except (ValueError, KeyError, TypeError):
            continue
        syms = sorted({m.decode() for m in SYMBOL.findall(body)})
        out[name] = " ".join(syms)
    return out


def load(src, kernels: dict | None = None) -> Trace:
    """Reduce a trace file (path) or its serialized bytes."""
    from jax.profiler import ProfileData
    data = ProfileData.from_serialized_xspace(src) \
        if isinstance(src, bytes) else ProfileData.from_file(src)
    ops, async_ops, spans = {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name not in (OPS_LINE, ASYNC_LINE):
                    continue
                dev = []
                for ev in line.events:
                    name, opcode = parse_op(ev.name)
                    short = f"{name} {opcode}".strip()
                    syms = (kernels or {}).get(name)
                    dev.append((int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns), short,
                                f"{short} {syms}" if syms else short))
                into = ops if line.name == OPS_LINE else async_ops
                into[int(m.group(1))] = sorted(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((int(ev.start_ns),
                                      int(ev.start_ns + ev.duration_ns),
                                      ev.name[len(SPAN_PREFIX):]))
    spans.sort()
    data_starts = [s for s, _, n in spans if n == "data"]
    if len(data_starts) < 2:
        raise ValueError("the trace holds fewer than two bench/data spans")
    return Trace(ops=ops, async_ops=async_ops, spans=spans,
                 window=(data_starts[0], data_starts[-1]),
                 steps=len(data_starts) - 1)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals) -> list:
    """Merged, sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Parts of merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


# ---------------------------------------------------------------------------
# shared readings
# ---------------------------------------------------------------------------

def busy_ns(tr: Trace, dev) -> int:
    return length(union((s, e) for s, e, _, _ in tr.clipped(dev)))


def is_collective(name: str) -> bool:
    """Whether an op (``%name opcode``) is a collective."""
    return COLLECTIVE.match(name.rpartition(" ")[2]) is not None


def collective_intervals(tr: Trace, dev) -> list:
    """Merged intervals in which a collective runs or is in flight."""
    return union([(s, e) for line in (OPS_LINE, ASYNC_LINE)
                  for s, e, name, _ in tr.clipped(dev, line)
                  if is_collective(name)])


def idle_gaps(tr: Trace, dev) -> list:
    """``(start, end, host span name)`` of each gap in the device's work."""
    busy = union((s, e) for s, e, _, _ in tr.clipped(dev))
    gaps = subtract([list(tr.window)], busy)
    out = []
    for s, e in gaps:
        mid = (s + e) // 2
        name = "none"
        for ss, se, sn in tr.spans:
            if ss <= mid < se:
                name = sn
        out.append((s, e, name))
    return out


CONTAINERS = ("while", "conditional", "call")


def op_seconds(tr: Trace) -> dict:
    """Seconds per op name inside the window, averaged over devices.  A
    loop's or call's event spans the ops of its body, which have events
    of their own, so containers are left out."""
    tot = defaultdict(int)
    for dev in tr.ops:
        for s, e, name, _ in tr.clipped(dev):
            if name.rpartition(" ")[2] not in CONTAINERS:
                tot[name] += e - s
    n = max(len(tr.ops), 1)
    return {k: v * 1e-9 / n for k, v in tot.items()}


def breakdown(tr: Trace, top: int = 10) -> dict:
    ops = sorted(op_seconds(tr).items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    for dev in tr.ops:
        gaps += [(e - s, name) for s, e, name in idle_gaps(tr, dev)]
    gaps.sort(reverse=True)
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[name, ns * 1e-9] for ns, name in gaps[:top]]}
