"""codec_kernel_ms: device time per step of the TACO codec's Pallas
kernels, averaged over the cell's chips.

A device op counts when its name or descriptive stats name one of
``KERNELS`` as a whole word: the block kernels that the TPU path runs
(``kernels/ash_compress.py``, ``kernels/ash_decompress.py``) and the
fused wire kernels, should a later change put them on the path.
"""
import re

KERNELS = ("_compress_kernel", "_decompress_kernel",
           "_decompress_reduce_kernel", "_compress_wire_kernel",
           "_decompress_wire_kernel", "_decompress_reduce_wire_kernel")
MATCH = re.compile(r"(?<![A-Za-z0-9_])(" + "|".join(KERNELS)
                   + r")(?![A-Za-z0-9_])")


def kernel_of(label: str):
    m = MATCH.search(label)
    return m.group(1) if m else None


def per_kernel_ns(tr) -> dict:
    """``kernel -> (summed ns over all devices, calls)`` in the window."""
    out = {}
    for dev in tr.ops:
        for s, e, _, label in tr.clipped(dev):
            k = kernel_of(label)
            if k:
                ns, n = out.get(k, (0, 0))
                out[k] = (ns + e - s, n + 1)
    return out


def read(run):
    tr = run.trace
    if tr is None or not tr.ops:
        return None
    per = per_kernel_ns(tr)
    if not per:
        return None
    total = sum(ns for ns, _ in per.values())
    return total * 1e-6 / len(tr.ops) / tr.steps
