"""Serving launcher: load (or init) a model and drive the
continuous-batching engine (``repro.serve.engine``) over a synthetic
Poisson arrival stream.

Requests arrive at ``--qps``, are admitted into a fixed ``--max-batch``
slot table (finished sequences retire and queued ones join BETWEEN jit'd
decode steps — the compiled step is never retraced), prompts prefill in
bucketed chunks disaggregated from decode, and every TP hop of the
decode path runs through the compressed collectives selected by
``--comm-spec``.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
        --qps 16 --requests 8 --max-batch 4 --gen 16 --comm-spec taco
"""
from __future__ import annotations

import argparse
import collections
import time

import jax
import numpy as np

from repro import compat
from repro.ckpt import checkpoint as ck
from repro.configs import get_config, make_plan, smoke_config
from repro.core.parallel import ParallelCtx
from repro.core.registry import from_spec, to_spec
from repro.launch._args import add_policy_alias, resolve_comm_spec
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh, mesh_axis_info
from repro.models.model import Model
from repro.serve.engine import ServeEngine


def build_engine(args, mesh):
    fsdp_axes, tp_axis, tp, fsdp = mesh_axis_info(mesh)
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    plan = make_plan(cfg, tp, fsdp, remat=False, kv_strategy=args.kv)
    model = Model(cfg, plan, fsdp_axes=fsdp_axes, tp_axis=tp_axis)
    comm_plan = from_spec(resolve_comm_spec(args))
    print(f"serving with comm spec: {to_spec(comm_plan)}")
    ragged = [p for p, v in comm_plan.wire_variable().items() if v]
    if ragged:
        print("variable wire layout on: " + ", ".join(ragged)
              + " (slot bound moved on the wire; achieved bytes are "
                "data-dependent — see docs/COMPRESSION.md)")
    ctx = ParallelCtx(tp_axis=tp_axis, fsdp_axes=fsdp_axes,
                      plan=comm_plan, tp_mode="allreduce")

    from jax.sharding import NamedSharding
    params = model.init(jax.random.PRNGKey(0))
    if args.ckpt:
        trained_spec = ck.read_comm_spec(args.ckpt)
        if trained_spec is not None:
            # serving may legitimately use a different decode plan than the
            # one trained with — surface it rather than hard-failing
            print(f"checkpoint was trained with comm spec: {trained_spec}")
        params, step = ck.restore(args.ckpt, params, mesh=mesh,
                                  pspecs=model.partition_specs())
        params = params["params"] if isinstance(params, dict) and \
            "params" in params else params
        print(f"restored checkpoint step {step}")
    pspecs = model.partition_specs()
    params = compat.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, pspecs)

    max_len = max(args.max_len, args.prompt_len + args.gen + 1)
    buckets = tuple(sorted({min(8, args.prompt_len),
                            min(32, max(args.prompt_len, 1))}))
    return ServeEngine(model, mesh, ctx, params,
                       max_batch=args.max_batch, max_len=max_len,
                       prefill_buckets=buckets), cfg


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced config (CPU-sized); --no-smoke for full")
    ap.add_argument("--mesh", default="1,1,1")
    ap.add_argument("--comm-spec", default=None, dest="comm_spec",
                    help="compression plan spec or alias, e.g. "
                         "'tp=taco:chunks=4' for the chunked ring-overlap "
                         "decode transport (see docs/COMPRESSION.md)")
    add_policy_alias(ap)
    ap.add_argument("--qps", type=float, default=16.0,
                    help="synthetic Poisson arrival rate (requests/s)")
    ap.add_argument("--requests", type=int, default=8,
                    help="total synthetic requests to serve")
    ap.add_argument("--max-batch", type=int, default=4, dest="max_batch",
                    help="slot-table rows (in-flight decode batch)")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--gen", type=int, default=16,
                    help="new tokens per request")
    ap.add_argument("--max-len", type=int, default=64, dest="max_len")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None,
                    help="restore params from a checkpoint dir")
    ap.add_argument("--kv", default="auto", choices=["auto", "pad_shard"])
    args = ap.parse_args()
    enable_compile_cache()

    shape = tuple(int(x) for x in args.mesh.split(","))
    mesh = make_mesh(shape, ("pod", "data", "model"))
    eng, cfg = build_engine(args, mesh)

    rng = np.random.default_rng(args.seed)
    arrivals = np.cumsum(rng.exponential(1.0 / args.qps, args.requests))
    pending = collections.deque(
        (float(t),
         rng.integers(0, cfg.vocab_size, args.prompt_len).astype(np.int32))
        for t in arrivals)

    t0 = time.monotonic()
    while pending or not eng.sched.idle():
        now = time.monotonic() - t0
        while pending and pending[0][0] <= now:
            t_arr, prompt = pending.popleft()
            eng.submit(prompt, max_new=args.gen, now=t_arr)
        # the engine runs on its own real clock (no explicit now=), so
        # first-token stamps land AFTER the prefill device work
        if not eng.tick() and pending:
            # engine idle, next arrival still in the future: wait for it
            time.sleep(max(0.0, pending[0][0] - now))

    for row in eng.reporter.of_kind("serve/request"):
        print("request rid={rid} prompt={prompt_len} new={new_tokens} "
              "queue={queue_s:.4f}s ttft={ttft_s:.4f}s "
              "decode={ms:.2f}ms/tok wire={wire_bytes_per_tok:.0f}B/tok"
              .format(ms=row["decode_s_per_tok"] * 1e3
                      if row["decode_s_per_tok"] else float("nan"), **row))
    s = eng.summary()
    wall = time.monotonic() - t0
    print(f"served {s['requests']} requests / "
          f"{s.get('total_new_tokens', 0)} tokens in {wall:.2f}s "
          f"({s.get('total_new_tokens', 0) / wall:.1f} tok/s), "
          f"p50 {s.get('decode_ms_per_tok_p50', float('nan')):.2f} "
          f"p99 {s.get('decode_ms_per_tok_p99', float('nan')):.2f} ms/tok, "
          f"recompiles after warmup: {s['recompiles']}")
    print("serving done")


if __name__ == "__main__":
    main()
