"""Chip smoke test: the TACO training path, end to end, on a TPU.

    python chip_smoke.py             # one chip: qwen2-0.5b, full width/depth
    python chip_smoke.py --chips 4   # four chips: gpt-6.7b widths at tp=4

One chip:
  1. kernel parity: the Pallas compress / decompress / decompress-reduce
     kernels against the f32 reference (``repro.kernels.ref``) at a real
     hop size (4M elements), within the tolerances of
     ``tests/test_kernels.py``;
  2. qwen2-0.5b (24 layers, d_model 896, vocab 151936, random weights
     from a seed) trained for a few steps through ``Trainer`` on a
     (1,1,1) mesh under ``--comm-spec taco`` and then ``baseline``.  Each
     spec prints its compile time, ``memory_analysis()``, the number of
     ``tpu_custom_call`` ops in the compiled step, its losses and its
     step time (host clock around ``block_until_ready``; a smoke timing,
     not a benchmark).

``--chips 4`` runs only the tensor-parallel path: gpt-6.7b at its
published widths (d_model 4096, 32 heads, d_ff 16384), depth cut to 8 of
its 32 layers so that the step fits 16 GB per chip, on mesh (1,1,4) under
``tp=none`` and ``taco``, with their losses compared.

The script fails (non-zero exit, no result line) when JAX finds no TPU;
it never falls back to the CPU.  It appends to ``LIBTPU_INIT_ARGS`` and
``XLA_FLAGS`` at most, and never overwrites them.  The last line of its
output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# Kernel-parity tolerances of tests/test_kernels.py.
ALPHA_RTOL = SCALE_RTOL = 1e-5
PAYLOAD_MISMATCH_MAX = 0.01
DECODE_RTOL, DECODE_ATOL = 1e-4, 1e-5
# taco vs uncompressed loss, per step (tests/test_train.py's 2% bound).
LOSS_REL_TOL = 0.02


def fail(msg: str):
    print(f"FAIL: {msg}", flush=True)
    sys.exit(1)


def require_tpu():
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX found {devs[0].platform!r} devices "
             f"({devs[0].device_kind}); this smoke test runs on a chip only")
    return devs


# --------------------------------------------------------------------------
# kernel parity
# --------------------------------------------------------------------------

def kernel_parity(blocks: int = 16384, block: int = 256, peers: int = 4):
    import jax
    import jax.numpy as jnp

    from repro.core.taco import TacoConfig
    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    x = rng.normal(0.0, 0.02, (blocks, block)).astype(np.float32)
    k = blocks * block // 500          # 0.2% heavy-tailed outliers
    idx = rng.choice(x.size, k, replace=False)
    x.reshape(-1)[idx] = rng.normal(0.0, 2.0, k).astype(np.float32)
    x = jnp.asarray(x)
    kern = TacoConfig(block_size=block, impl="pallas")
    oracle = TacoConfig(block_size=block, impl="jnp")

    def close(name, got, want, rtol, atol=0.0):
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        err = np.abs(got - want)
        bad = err > atol + rtol * np.abs(want)
        print(f"  parity {name}: max_abs_err={err.max():.3e} "
              f"violations={int(bad.sum())}/{bad.size}", flush=True)
        if bad.any():
            fail(f"kernel parity {name} outside rtol={rtol} atol={atol}")

    # the reference is f32 semantics: its dots run at f32 precision (the
    # kernels state HIGHEST; XLA's TPU default would round to bf16)
    with jax.default_matmul_precision("highest"):
        qr, ar, sr = jax.jit(lambda v: ref.compress_blocks_ref(v, oracle))(x)
        qk, ak, sk = jax.jit(lambda v: ops.compress_blocks(v, kern))(x)
        close("compress.alpha", ak, ar, ALPHA_RTOL)
        close("compress.scale", sk, sr, SCALE_RTOL)
        mism = float(np.mean(np.asarray(qk.astype(jnp.float32))
                             != np.asarray(qr.astype(jnp.float32))))
        print(f"  parity compress.payload: mismatch_frac={mism:.2e}",
              flush=True)
        if mism >= PAYLOAD_MISMATCH_MAX:
            fail(f"kernel parity compress.payload mismatch {mism}")
        for meta, (s_in, a_in) in (("dual", (sr, ar)),
                                   ("folded", (sr / ar[:, None], None))):
            got = jax.jit(lambda q, s, a: ops.decompress_blocks(
                q, s, a, kern))(qr, s_in, a_in)
            want = jax.jit(lambda q, s, a: ref.decompress_blocks_ref(
                q, s, a, oracle))(qr, s_in, a_in)
            close(f"decompress[{meta}]", got, want, DECODE_RTOL, DECODE_ATOL)
        m = blocks // peers
        q3, s3 = qr.reshape(peers, m, block), sr.reshape(peers, m, 1)
        a3 = ar.reshape(peers, m)
        got = jax.jit(lambda q, s, a: ops.decompress_reduce(
            q, s, a, kern))(q3, s3, a3)
        want = jax.jit(lambda q, s, a: ref.decompress_reduce_ref(
            q, s, a, oracle))(q3, s3, a3)
        close("decompress_reduce", got, want, DECODE_RTOL, DECODE_ATOL)
    print(f"kernel parity ok at hop size {blocks * block} elements",
          flush=True)


# --------------------------------------------------------------------------
# training
# --------------------------------------------------------------------------

def abstract_state(trainer, seq: int, batch: int):
    """Sharded ShapeDtypeStructs of (params, opt_state, batch)."""
    import jax
    from jax.sharding import NamedSharding

    from repro import compat
    from repro.optim import adamw

    model, mesh = trainer.model, trainer.mesh

    def sds(a, spec):
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=NamedSharding(mesh, spec))

    pspecs = model.partition_specs()
    abstract = model.abstract_params()
    params = compat.tree_map(sds, abstract, pspecs)
    opt = compat.tree_map(sds, adamw.abstract_opt_state(abstract),
                          adamw.opt_state_pspecs(pspecs))
    bspecs = model.batch_pspecs()
    data = {k: sds(v, bspecs[k])
            for k, v in model.batch_shape(seq, batch).items()}
    return params, opt, data


def train_phase(cfg, *, mesh: str, spec: str, seq: int, batch: int,
                steps: int, timed: int = 3) -> dict:
    """Compile, then run ``steps`` Trainer steps and ``timed`` more timed
    steps of ``cfg`` under ``spec``; prints and returns the readings."""
    import jax

    from repro.launch.train import build_parser, build_trainer

    args = build_parser().parse_args([
        "--arch", cfg.name, "--mesh", mesh, "--comm-spec", spec,
        "--seq", str(seq), "--batch", str(batch), "--steps", str(steps),
        "--ckpt", "", "--no-resume"])
    trainer = build_trainer(args, cfg)
    fn, _ = trainer.step_fn_for(0)

    t0 = time.perf_counter()
    compiled = fn.lower(*abstract_state(trainer, seq, batch)).compile()
    compile_s = time.perf_counter() - t0
    kernels = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    ma = compiled.memory_analysis()
    peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    print(f"[{spec}] compile {compile_s:.1f}s; memory_analysis: "
          f"args {ma.argument_size_in_bytes / 1e9:.2f} GB, temp "
          f"{ma.temp_size_in_bytes / 1e9:.2f} GB, peak ~{peak / 1e9:.2f} GB "
          f"per chip; tpu_custom_call ops in the step: {kernels}",
          flush=True)
    del compiled

    t0 = time.perf_counter()
    params, opt, losses = trainer.run(resume=False)
    run_s = time.perf_counter() - t0

    fn, _ = trainer.step_fn_for(steps)
    data = trainer.data.place(trainer.data.batch(steps), trainer.mesh,
                              trainer.model.batch_pspecs())
    times = []
    for _ in range(timed):
        t0 = time.perf_counter()
        params, opt, metrics = fn(params, opt, data)
        jax.block_until_ready((params, opt, metrics))
        times.append(time.perf_counter() - t0)
    losses = list(losses) + [float(metrics["loss"])]
    del params, opt, metrics, trainer
    step_s = float(np.median(times))
    tokens = seq * batch
    print(f"[{spec}] losses {losses}; {steps} Trainer steps in "
          f"{run_s:.1f}s (the first compiles or loads the step); smoke "
          f"step times {times} s, median {step_s} s "
          f"({tokens / step_s:.0f} tokens/s)", flush=True)
    if not all(math.isfinite(v) for v in losses):
        fail(f"[{spec}] non-finite loss: {losses}")
    return {"spec": spec, "compile_s": compile_s, "kernels": kernels,
            "losses": losses, "step_s": step_s, "peak_bytes": peak}


def compare_losses(a: dict, b: dict):
    rel = [abs(x - y) / abs(y) for x, y in zip(a["losses"], b["losses"])]
    print(f"loss [{a['spec']}] vs [{b['spec']}]: max rel diff "
          f"{max(rel):.2e} (limit {LOSS_REL_TOL})", flush=True)
    if max(rel) > LOSS_REL_TOL:
        fail(f"{a['spec']} losses {a['losses']} diverge from "
             f"{b['spec']} {b['losses']}")


def one_chip(steps: int):
    from repro.configs import get_config

    kernel_parity()
    cfg = get_config("qwen2-0.5b")
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"vocab {cfg.vocab_size} (published widths, full depth)",
          flush=True)
    # seq 4096 x batch 1: ~11.5 GB per memory_analysis (bf16 params + f32
    # master + two f32 Adam moments ~6.9 GB; the f32 logits bound batch)
    taco = train_phase(cfg, mesh="1,1,1", spec="taco", seq=4096, batch=1,
                       steps=steps)
    if taco["kernels"] == 0:
        fail("the taco step holds no tpu_custom_call: kernels not on the path")
    base = train_phase(cfg, mesh="1,1,1", spec="baseline", seq=4096,
                       batch=1, steps=steps)
    compare_losses(taco, base)


def four_chips(steps: int, layers: int = 8):
    from repro.configs import get_config

    full = get_config("gpt-6.7b")
    cfg = dataclasses.replace(full, n_layers=layers)
    print(f"model {cfg.name}: d_model {cfg.d_model}, {cfg.n_heads} heads, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}; depth cut to "
          f"{layers} of {full.n_layers} layers to fit 16 GB per chip; "
          f"mesh (1,1,4) = tp 4", flush=True)
    none = train_phase(cfg, mesh="1,1,4", spec="tp=none", seq=2048,
                       batch=1, steps=steps)
    taco = train_phase(cfg, mesh="1,1,4", spec="taco", seq=2048, batch=1,
                       steps=steps)
    if taco["kernels"] == 0:
        fail("the taco step holds no tpu_custom_call: kernels not on the path")
    compare_losses(taco, none)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the tensor-parallel path on a 2x2 host")
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args()

    devs = require_tpu()
    if len(devs) < args.chips:
        fail(f"--chips {args.chips} but JAX sees {len(devs)} device(s)")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
          f"compile cache {enable_compile_cache()}", flush=True)
    if args.chips == 4:
        four_chips(args.steps)
    else:
        one_chip(args.steps)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
