"""codec_kernel_roofline: the least time the chip needs for one step's
codec work, over the codec kernels' measured time (``codec_kernel_ms``).

The work is counted from each tensor-parallel hop's shape, whatever
implements it.  A hop moves a full activation of ``N = batch*seq*d_model``
elements over ``P`` chips in blocks of ``B``; each block carries one byte
per element and the wire's ``scale_bytes_per_block`` (the configuration's
``tp_wire``):

* all-gather: encode the local ``N/P`` (read bf16, write payload and
  scales, rotate: ``2*n*B`` FLOPs), decode all ``N`` (read payload and
  scales, rotate, write bf16);
* reduce-scatter: encode all ``N``, then sum the ``P`` received parts in
  the rotated domain and rotate once (``2*(N/P)*B`` FLOPs), writing the
  ``N/P`` bf16 result.

Per step, each transformer layer has two gather and two scatter sites
(attention and MLP).  The forward runs all four; the program's full
rematerialisation
runs again the three whose results the backward needs (the MLP's exit
scatter only leaves the block, so the compiler drops its recomputation);
the backward runs each site's conjugate (a gather's cotangent is
scattered, a scatter's gathered).  The embedding adds a scatter and its
backward gather, the final norm a gather and its backward scatter.  On
gpt-6.7b-l8 that is 50 gathers and 42 scatters, as the trace counts
decompress and decompress-reduce calls.  Each call's least time is the larger of its FLOPs at
the bf16 peak and its bytes at the HBM bandwidth; ``bound`` says which
binds over the step.
"""
from metrics import codec_kernel_ms


def hops(run) -> tuple[int, int]:
    """(gathers, scatters) per step."""
    layers = run.arch["n_layers"]
    again = run.remat == "full"
    gathers = 2 * layers * (2 if again else 1) + 2 * layers + 2
    scatters = layers * (3 if again else 2) + 2 * layers + 2
    return gathers, scatters


def calls(run) -> list[tuple[float, float]]:
    """``(flops, bytes)`` of every codec call of one step, on one chip."""
    b, meta = run.wire["block"], run.wire["scale_bytes_per_block"]
    p = run.tp
    n = run.batch * run.seq * run.arch["d_model"]

    def enc(m):
        return 2.0 * m * b, 2.0 * m + m + meta * m / b

    def dec(m, out):
        return 2.0 * out * b, m + meta * m / b + 2.0 * out

    ag, rs = hops(run)
    return ([enc(n / p), dec(n, n)] * ag) + ([enc(n), dec(n, n / p)] * rs)


def least_s(run) -> tuple[float, str]:
    pf, pb = run.peaks["bf16_flops"], run.peaks["hbm_bytes_per_s"]
    t, by_flops = 0.0, 0.0
    for f, by in calls(run):
        t += max(f / pf, by / pb)
        if f / pf > by / pb:
            by_flops += f / pf
    return t, ("flops" if by_flops > t / 2 else "bytes")


def bound(run) -> str:
    return least_s(run)[1]


def read(run):
    ms = codec_kernel_ms.read(run)
    if not ms or not run.wire or not run.peaks:
        return None
    return 100.0 * least_s(run)[0] / (ms * 1e-3)
