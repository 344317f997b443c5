import os
# A CPU rehearsal tool: pin it to the CPU backend (it must never take a
# chip) and append the 512-device placeholder world to the caller's flags.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512"
                           ).strip()

"""Multi-pod dry-run (deliverable e) + roofline extraction (deliverable g).

Meshes (spec-mandated, built by launch/mesh.py):
  single-pod : (16, 16)      ("data", "model")        256 chips
  multi-pod  : (2, 16, 16)   ("pod", "data", "model") 512 chips

The 512-device placeholder world is forced by the XLA_FLAGS lines ABOVE ALL
IMPORTS (jax locks the device count on first init; nothing else in the
repo sets this globally — smoke tests and benches see 1 device), which
also pin the process to the CPU backend.

Modes:
  --mode check     lower+compile the production config (scan-over-layers,
                   the true runtime artifact); print memory_analysis +
                   cost_analysis. This is the pass/fail gate.
  --mode roofline  check + DEPTH EXTRAPOLATION: XLA cost analysis counts a
                   lax.scan body once, hiding (L-1)/L of the per-step
                   flops/bytes/collectives, so we additionally compile 2-3
                   depth-reduced UNROLLED variants and solve the (exactly
                   linear) per-layer-type cost model
                       term = base + sum_k slope_k * n_layers_k
                   to recover true full-depth roofline terms.

Usage:
  python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k \
      --mesh single --policy taco --mode roofline --out results/dryrun
  python -m repro.launch.dryrun --all --mesh multi --mode check
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.configs import (ASSIGNED, SHAPES, applicable, get_config,
                           make_plan)
from repro.core import registry
from repro.core.parallel import ParallelCtx
from repro.launch import roofline as rl
from repro.launch.mesh import (make_production_mesh, mesh_axis_info,
                               sp_axis_info)
from repro.models.model import Model
from repro.optim import adamw

# name-only aliases here pin impl=jnp (the host-CPU placeholder devices)
# but otherwise mean exactly what the registry aliases mean; any full
# registry spec string is also accepted verbatim by --policy
_LOCAL_ALIASES = {
    "taco": "tp=taco:jnp",
    "taco3d": "tp=taco:jnp,grad_rs=sdp4bit,pp=tahquant",
    "taco_folded": "tp=taco:jnp:folded",
}


def build_policy(name: str):
    return registry.from_spec(_LOCAL_ALIASES.get(name, name))


def input_specs(model, suite):
    """ShapeDtypeStruct stand-ins for every input of the lowered step —
    weak-type-correct, shardable, zero allocation."""
    if suite.kind == "train":
        params = model.abstract_params()
        opt = adamw.abstract_opt_state(params)
        batch = model.batch_shape(suite.seq_len, suite.global_batch)
        return (params, opt, batch)
    from repro.serve import serve_step as ss
    params = model.abstract_params()
    cache = ss.cache_shapes(model, suite.global_batch, suite.seq_len)
    token = jax.ShapeDtypeStruct((suite.global_batch, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return (params, cache, token, pos)


def build_serve(model, mesh, ctx, shard_batch: bool):
    from repro.compat import shard_map
    from repro.serve import serve_step as ss

    pspecs = model.partition_specs()
    cspecs = ss.cache_pspecs(model)
    dp = model.fsdp_axes if len(model.fsdp_axes) > 1 else \
        (model.fsdp_axes[0] if model.fsdp_axes else None)
    if not shard_batch:  # e.g. long_500k: global_batch=1 stays replicated
        dp = None
        cspecs = compat.tree_map(
            lambda s: P(*((s[0],) + (None,) + tuple(s[2:]))), cspecs,
            is_leaf=lambda s: isinstance(s, P))

    def step(params, cache, token, pos):
        return ss.decode_forward(params, token, cache, pos, model, ctx)

    sharded = shard_map(step, mesh=mesh,
                        in_specs=(pspecs, cspecs, P(dp), P()),
                        out_specs=(P(dp), cspecs), check_vma=False)
    return jax.jit(sharded)


def parse_variant(variant: str | None) -> dict:
    """'remat=dots,kv=pad_shard,attnf32=off,wag=int8' -> option dict."""
    out = {"remat_policy": "full", "kv_strategy": "auto",
           "attn_f32": True, "wag_int8": False}
    if not variant:
        return out
    for part in variant.split(","):
        k, v = part.split("=")
        if k == "remat":
            out["remat_policy"] = v
        elif k == "kv":
            out["kv_strategy"] = v
        elif k == "attnf32":
            out["attn_f32"] = v not in ("off", "0", "false")
        elif k == "wag":
            out["wag_int8"] = (v == "int8")
        else:
            raise ValueError(part)
    return out


def lower_cell(cfg, shape: str, mesh_kind: str, policy_name: str,
               *, tp_mode=None, remat=True, scan_layers=True, variant=None,
               sp=1, sp_mode="ulysses"):
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"), sp=sp)
    fsdp_axes, tp_axis, tp, fsdp = mesh_axis_info(mesh)
    sp_axis, sp = sp_axis_info(mesh)
    suite = SHAPES[shape]
    if suite.kind != "train" and sp > 1:
        raise ValueError("--sp applies to train shapes only (the serve "
                         "path decodes without a sequence axis to shard)")
    if suite.seq_len % max(sp, 1):
        raise ValueError(f"shape {shape} seq_len {suite.seq_len} not "
                         f"divisible by sp={sp}")
    vopts = parse_variant(variant)
    plan = make_plan(cfg, tp, fsdp, remat=remat, scan_layers=scan_layers,
                     remat_policy=vopts["remat_policy"],
                     kv_strategy=vopts["kv_strategy"],
                     attn_f32=vopts["attn_f32"])
    model = Model(cfg, plan, fsdp_axes=fsdp_axes, tp_axis=tp_axis,
                  sp_axis=sp_axis)
    policy = build_policy(policy_name)
    if vopts["wag_int8"]:
        import dataclasses as _dc
        policy = _dc.replace(policy,
                             weight_ag=registry.codec_from_spec("int8"))
    mode = tp_mode or ("sp" if suite.kind == "train" else "allreduce")
    ctx = ParallelCtx(tp_axis=tp_axis, fsdp_axes=fsdp_axes, plan=policy,
                      tp_mode=mode, sp_axis=sp_axis, sp_mode=sp_mode)

    if suite.kind == "train":
        from repro.train.train_step import build_train_step
        step = build_train_step(model, mesh, ctx, adamw.OptConfig(),
                                donate=False)
    else:
        step = build_serve(model, mesh, ctx,
                           shard_batch=(suite.global_batch % fsdp == 0))
    specs = input_specs(model, suite)
    t0 = time.time()
    lowered = step.lower(*specs)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    meta = {"tp_mode": mode, "sp": sp, "sp_mode": sp_mode if sp > 1 else None,
            "devices": mesh.size, "variant": variant,
            "lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2),
            "plan": {"tp": plan.tp, "fsdp": plan.fsdp,
                     "heads_pad": plan.heads_pad, "kv_mode": plan.kv_mode,
                     "vocab_pad": plan.vocab_pad}}
    return lowered, compiled, meta, model, suite


# --------------------------------------------------------------------------
# depth extrapolation
# --------------------------------------------------------------------------

def _layer_types(cfg):
    if cfg.family == "hybrid" and cfg.hybrid_full_attn:
        return ["swa", "full"]
    if cfg.family == "encdec":
        return ["enc", "dec"]
    return ["layer"]


def _variant_cfg(cfg, counts: dict):
    """Config with the given per-type layer counts."""
    if cfg.family == "hybrid" and cfg.hybrid_full_attn:
        f, s = counts["full"], counts["swa"]
        return dataclasses.replace(cfg, n_layers=f + s,
                                   hybrid_full_attn=tuple(range(f)))
    if cfg.family == "encdec":
        return dataclasses.replace(cfg, enc_layers=counts["enc"],
                                   n_layers=counts["dec"])
    return dataclasses.replace(cfg, n_layers=counts["layer"])


def _real_counts(cfg):
    if cfg.family == "hybrid" and cfg.hybrid_full_attn:
        f = len(cfg.hybrid_full_attn)
        return {"full": f, "swa": cfg.n_layers - f}
    if cfg.family == "encdec":
        return {"enc": cfg.enc_layers, "dec": cfg.n_layers}
    return {"layer": cfg.n_layers}


def _variant_points(types):
    if len(types) == 1:
        return [{types[0]: 1}, {types[0]: 2}]
    a, b = types
    return [{a: 1, b: 1}, {a: 2, b: 1}, {a: 1, b: 2}]


def _metrics_of(compiled, n_devices):
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    colls = rl.parse_collectives(compiled.as_text(), n_devices)
    out = {"flops": float(cost.get("flops", 0.0)),
           "hbm": float(cost.get("bytes accessed", 0.0)),
           "link": colls.link_bytes_per_device}
    for k, v in colls.bytes_by_kind.items():
        out[f"coll:{k}"] = v
    return out


def extrapolate_roofline(cfg, shape, mesh_kind, policy_name, tp_mode=None,
                         variant=None):
    """Solve term = base + sum_k slope_k * n_k from unrolled depth-reduced
    compiles; return full-depth metrics + the fit details."""
    from repro.models import analysis_mode
    types = _layer_types(cfg)
    points = _variant_points(types)
    rows, metrics = [], []
    for counts in points:
        vcfg = _variant_cfg(cfg, counts)
        with analysis_mode.enabled():
            _, compiled, meta, _, _ = lower_cell(
                vcfg, shape, mesh_kind, policy_name,
                tp_mode=tp_mode, scan_layers=False, variant=variant)
        rows.append([1.0] + [float(counts[t]) for t in types])
        metrics.append(_metrics_of(compiled, meta["devices"]))
    keys = sorted({k for m in metrics for k in m})
    a = np.array(rows)
    real = _real_counts(cfg)
    x_real = np.array([1.0] + [float(real[t]) for t in types])
    full = {}
    for k in keys:
        y = np.array([m.get(k, 0.0) for m in metrics])
        coef, *_ = np.linalg.lstsq(a, y, rcond=None)
        full[k] = float(max(np.dot(coef, x_real), 0.0))
    return full, {"points": [dict(p) for p in points], "types": types}


# --------------------------------------------------------------------------
# cell runner
# --------------------------------------------------------------------------

def model_flops_for(cfg, suite) -> float:
    n = cfg.active_param_count()
    if suite.kind == "train":
        return 6.0 * n * suite.seq_len * suite.global_batch
    return 2.0 * n * suite.global_batch  # one token per sequence


def run_cell(arch, shape, mesh_kind, policy_name, out_dir=None, *,
             mode="check", tp_mode=None, variant=None, sp=1,
             sp_mode="ulysses"):
    cfg = get_config(arch)
    ok, reason = applicable(cfg, shape)
    suite = SHAPES[shape]
    if ok and sp > 1 and suite.kind != "train":
        ok, reason = False, ("--sp shards the train sequence axis; the "
                             "serve path decodes without one")
    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
           "policy": policy_name, "mode": mode}
    if sp > 1:
        rec["sp"] = sp
    if not ok:
        rec.update({"status": "skipped", "reason": reason})
        print(f"SKIP  {arch:28s} {shape:12s} {mesh_kind:6s} — {reason}",
              flush=True)
    else:
        try:
            t_all = time.time()
            lowered, compiled, meta, model, suite = lower_cell(
                cfg, shape, mesh_kind, policy_name, tp_mode=tp_mode,
                scan_layers=True, variant=variant, sp=sp, sp_mode=sp_mode)
            mem = compiled.memory_analysis()
            print(f"--- memory_analysis [{arch} {shape} {mesh_kind}] ---")
            print(mem)
            rec.update({"status": "ok", **meta})
            rec["memory"] = {
                k: int(getattr(mem, k)) for k in
                ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes")
                if hasattr(mem, k)}
            if mode == "roofline":
                full, fit = extrapolate_roofline(
                    cfg, shape, mesh_kind, policy_name, tp_mode, variant)
                chips = meta["devices"]
                mf = model_flops_for(cfg, suite)
                compute_s = full["flops"] / rl.PEAK_FLOPS
                memory_s = full["hbm"] / rl.HBM_BW
                coll_s = full["link"] / rl.ICI_BW
                terms = {"compute": compute_s, "memory": memory_s,
                         "collective": coll_s}
                dom = max(terms, key=terms.get)
                rec["roofline"] = {
                    "per_device_flops": full["flops"],
                    "per_device_hbm_bytes": full["hbm"],
                    "per_device_link_bytes": full["link"],
                    "coll_by_kind": {k[5:]: v for k, v in full.items()
                                     if k.startswith("coll:")},
                    "compute_s": compute_s, "memory_s": memory_s,
                    "collective_s": coll_s, "dominant": dom,
                    "model_flops": mf,
                    "useful_ratio": mf / max(full["flops"] * chips, 1.0),
                    "fit": fit,
                }
                print(f"OK    {arch:28s} {shape:12s} {mesh_kind:6s} "
                      f"{policy_name:12s} wall={time.time()-t_all:6.1f}s "
                      f"compute={compute_s*1e3:9.2f}ms "
                      f"memory={memory_s*1e3:9.2f}ms "
                      f"coll={coll_s*1e3:9.2f}ms dom={dom} "
                      f"useful={rec['roofline']['useful_ratio']:.3f}",
                      flush=True)
            else:
                print(f"OK    {arch:28s} {shape:12s} {mesh_kind:6s} "
                      f"{policy_name:12s} compile={meta['compile_s']:6.1f}s",
                      flush=True)
        except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
            rec.update({"status": "error",
                        "error": f"{type(e).__name__}: {e}",
                        "traceback": traceback.format_exc()})
            print(f"ERROR {arch:28s} {shape:12s} {mesh_kind:6s} — "
                  f"{type(e).__name__}: {str(e)[:300]}", flush=True)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        vtag = "" if not variant else "__" + variant.replace(",", "+").replace("=", "-")
        ptag = policy_name.replace(",", "+").replace("=", "-").replace(":", ".")
        fn = f"{arch}__{shape}__{mesh_kind}__{ptag}__{mode}{vtag}.json"
        with open(os.path.join(out_dir, fn), "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--policy", default="taco",
                    help="comm-plan alias (baseline/taco/taco3d/"
                         "taco_folded) or a full registry spec string, "
                         "e.g. 'tp=taco:jnp,skip_first=2,skip_last=2'")
    ap.add_argument("--tp-mode", default=None)
    ap.add_argument("--sp", type=int, default=1,
                    help="sequence-parallel axis size; carves a 'seq' axis "
                         "out of the data axis of the production mesh "
                         "(train shapes only)")
    ap.add_argument("--sp-mode", default="ulysses", dest="sp_mode",
                    choices=["ulysses", "ring"])
    ap.add_argument("--mode", default="check",
                    choices=["check", "roofline"])
    ap.add_argument("--variant", default=None,
                    help="hillclimb knobs, e.g. remat=dots,kv=pad_shard")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    # --all expands only the dimensions not explicitly pinned
    archs = [args.arch] if args.arch else ASSIGNED
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    results = []
    for mesh_kind in meshes:
        for arch in archs:
            for shape in shapes:
                results.append(run_cell(arch, shape, mesh_kind, args.policy,
                                        args.out, mode=args.mode,
                                        tp_mode=args.tp_mode,
                                        variant=args.variant, sp=args.sp,
                                        sp_mode=args.sp_mode))
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\n== dry-run: {n_ok} ok, {n_skip} skipped (spec), {n_err} errors")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
