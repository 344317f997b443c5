"""Wire-packing + chunked-ring-overlap transport benchmark.

Two comparisons on real 8-device CPU meshes, the perf claims of the
single-buffer transport engine (`repro.core.collectives`):

  * single-buffer vs multi-buffer — the packed path issues ONE lax
    collective per compressed hop (payload+scale+alpha bitcast into one
    uint8 buffer) where the multi-buffer baseline issues 2-3; both are
    timed and their lowered-HLO collective counts recorded.  The primary
    rows use a latency-bound TP-intermediate-sized tensor — exactly the
    serialized low-latency collectives Flash Communication identifies as
    the TP bottleneck, where collapsing 3 launches into 1 wins (~1.5x on
    CPU at decode-like sizes); the ``*_bw_*`` rows record the
    bandwidth-bound regime where the pack/unpack copy shows up on CPU
    (real ICI hides it behind the transfer).
  * chunked ring vs monolithic — ``chunks=N`` ring transport built from
    ppermute steps over N wire slices vs the one-shot collective.  On CPU
    the ring pays for its extra launches (no async overlap to win back);
    the numbers exist to track that the decomposition overhead stays
    bounded, and the row is the baseline future async work improves on.
  * pipelined vs serial ring schedule — every ring row is PAIRED with a
    ``schedule=serial`` twin (``*_ring_cN`` vs ``*_ring_cN_serial``): the
    software-pipelined stage schedule (``repro.core.overlap``, barrier-
    fenced (encode[c], transfer[c-1], decode[c-2]) ticks) against the
    hoisted all-encodes-first emission.  The fences add no ops but DO
    constrain the synchronous CPU scheduler, which shows up as a small
    measured overhead on some hops (``vs_serial`` 0.87-1.02x at the
    committed baseline, worst on latency-bound reduce-scatter) — the
    paired rows pin that cost honestly, so an async/TPU backend where
    pipelined pulls ahead shows up as a tracked win rather than an
    anecdote, and a CPU regression where the fences get more expensive
    shows up too.
  * kernel-fused wire emission vs the pack copy — ``encode_wire`` /
    ``decode_wire`` running in the fused Pallas kernels (interpret mode
    on CPU: same HLO structure, payload+scales+alpha stored straight at
    their wire offsets, zero concatenates) vs the jnp copy path
    (``pack_wire`` bitcast-concat).  The CPU rows track the trajectory of
    the ``*_bw_*`` copy overhead the fusion eliminates; on TPU the fused
    kernel is the single-HBM-write path.

Timing collectives needs >1 device, and XLA device count is fixed at
process start, so ``run`` re-executes this module as a worker subprocess
pinned to the CPU with ``--xla_force_host_platform_device_count=8`` and
relays its rows.
"""
from __future__ import annotations

import os
import re
import subprocess
import sys
from pathlib import Path

from benchmarks.common import emit

REPO = Path(__file__).resolve().parents[1]
_COLLECTIVE = re.compile(
    r"stablehlo\.(all_gather|all_to_all|all_reduce|reduce_scatter"
    r"|collective_permute|collective_broadcast)\b")


def run(out_dir="results/bench", quick=False):
    env = dict(os.environ)
    # the worker measures CPU transport structure: pin it to the CPU so it
    # never contends for a chip this parent process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["PYTHONPATH"] = f"{REPO / 'src'}{os.pathsep}{REPO}" + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    cmd = [sys.executable, "-m", "benchmarks.overlap", "--worker"]
    if quick:
        cmd.append("--quick")
    proc = subprocess.run(cmd, env=env, cwd=REPO, capture_output=True,
                          text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(
            f"overlap worker failed:\n{proc.stdout}\n{proc.stderr}")
    for line in proc.stdout.splitlines():
        if line.startswith("overlap/"):
            name, us, derived = line.split(",", 2)
            emit(name, float(us) if us else None, derived)


# --------------------------------------------------------------------------
# worker (runs with 8 forced host devices)
# --------------------------------------------------------------------------

def _collective_count(jitted, *args) -> int:
    return len(_COLLECTIVE.findall(jitted.lower(*args).as_text()))


def _worker(quick: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from benchmarks.common import time_fn, tp_like_tensor
    from repro.compat import shard_map
    from repro.core import collectives as cc
    from repro.core.registry import codec_from_spec

    mesh = jax.make_mesh((8,), ("model",))
    rng = np.random.default_rng(7)
    # latency-bound: one decode-step TP intermediate (batch x hidden
    # sized) — the regime the fused single collective targets; full mode
    # tightens the median with more iters rather than growing the tensor
    # out of the latency-bound regime
    x_lat = tp_like_tensor(rng, (8, 1024))
    # bandwidth-bound: training-activation sized
    x_bw = tp_like_tensor(rng, (64, 2048) if quick else (256, 4096))
    iters = 10 if quick else 50

    from repro.core.registry import codec_to_spec

    identity = codec_from_spec("none")
    taco = codec_from_spec("taco:jnp")          # dual metadata: 3 components
    chunks = 4
    taco_ring = codec_from_spec(f"taco:jnp:chunks={chunks}")
    # fused wire-emission kernels (interpret mode on CPU)
    taco_fused = codec_from_spec("taco:pallas_interpret")

    def serial_twin(ring_codec):
        """Same codec + chunking, schedule=serial — derived through the
        spec grammar so the paired rows can never drift apart."""
        return codec_from_spec(codec_to_spec(ring_codec) + ":schedule=serial")

    def jit_sm(fn, in_spec, out_spec):
        return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_spec,
                                 out_specs=out_spec, check_vma=False))

    def ag(codec):
        return jit_sm(lambda v: cc.all_gather_c(v, "model", 0, codec,
                                                identity),
                      P("model"), P())

    def rs(codec):
        return jit_sm(lambda v: cc.psum_scatter_c(v, "model", 0, codec,
                                                  identity),
                      P(), P("model"))

    def measure(tag, x, make_fn, ring_codec):
        fn_packed = make_fn(taco)
        us_p = time_fn(fn_packed, x, iters=iters)
        n_p = _collective_count(fn_packed, x)
        with cc.multibuffer_wire():
            fn_m = make_fn(taco)
            n_m = _collective_count(fn_m, x)
            us_m = time_fn(fn_m, x, iters=iters)
        emit(f"overlap/{tag}_packed", us_p,
             f"collectives={n_p};vs_multibuf={us_m / us_p:.2f}x")
        emit(f"overlap/{tag}_multibuf", us_m,
             f"collectives={n_m};baseline")
        # kernel-fused wire emission vs the pack_wire copy (us_p above)
        fn_f = make_fn(taco_fused)
        us_f = time_fn(fn_f, x, iters=iters)
        n_f = _collective_count(fn_f, x)
        emit(f"overlap/{tag}_fusedwire", us_f,
             f"collectives={n_f};vs_copy={us_p / us_f:.2f}x")
        if ring_codec is not None:
            fn_r = make_fn(ring_codec)
            us_r = time_fn(fn_r, x, iters=iters)
            n_r = _collective_count(fn_r, x)
            # paired schedule rows: same chunking, same ring steps, only
            # the stage emission order (and its barrier fences) differs
            fn_s = make_fn(serial_twin(ring_codec))
            us_s = time_fn(fn_s, x, iters=iters)
            n_s = _collective_count(fn_s, x)
            emit(f"overlap/{tag}_ring_c{chunks}", us_r,
                 f"collectives={n_r};schedule=pipelined;"
                 f"vs_monolithic={us_p / us_r:.2f}x;"
                 f"vs_serial={us_s / us_r:.2f}x")
            emit(f"overlap/{tag}_ring_c{chunks}_serial", us_s,
                 f"collectives={n_s};schedule=serial;baseline")

    measure("all_gather", x_lat, ag, taco_ring)
    measure("reduce_scatter", x_lat, rs, taco_ring)
    measure("all_gather_bw", x_bw, ag, taco_ring)
    measure("reduce_scatter_bw", x_bw, rs, taco_ring)


if __name__ == "__main__":
    if "--worker" not in sys.argv:
        raise SystemExit("benchmarks.overlap runs via benchmarks.run, or "
                         "directly with --worker under forced host devices")
    _worker("--quick" in sys.argv)
