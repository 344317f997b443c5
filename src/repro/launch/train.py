"""Production training launcher.

On real hardware this runs under `python -m repro.launch.train` on every
host of the pod slice (jax.distributed handles cross-host init); on a CPU
it drives the same code path on small meshes.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --smoke --steps 50 --comm-spec "tp=taco,warmup=10"

``build_parser`` + ``build_trainer`` are the programmatic form of the same
entry point (``chip_smoke.py`` drives them).
"""
from __future__ import annotations

import argparse
import logging

from repro.configs import get_config, make_plan, smoke_config
from repro.core.parallel import ParallelCtx
from repro.core.registry import from_spec
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch._args import add_policy_alias, resolve_comm_spec
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import (SP_AXIS, make_mesh, mesh_axis_info,
                               sp_axis_info)
from repro.models.model import Model
from repro.optim.adamw import OptConfig
from repro.train.trainer import Trainer, TrainerConfig


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt-350m")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--mesh", default="1,1,1",
                    help="pod,data,model sizes (needs matching device count)")
    ap.add_argument("--sp", type=int, default=1,
                    help="sequence-parallel axis size; carves a 'seq' axis "
                         "out of the data axis (data must stay divisible). "
                         "Attention crosses it via the 'sp=' codec path "
                         "(--comm-spec \"sp=taco:folded\")")
    ap.add_argument("--sp-mode", default="ulysses", dest="sp_mode",
                    choices=["ulysses", "ring"],
                    help="sp attention flavor: Ulysses heads<->sequence "
                         "all-to-all, or blockwise ring over compressed "
                         "KV ppermute hops")
    ap.add_argument("--comm-spec", default=None, dest="comm_spec",
                    help="compression plan spec or alias, e.g. "
                         "'tp=taco:folded:chunks=4,grad_rs=sdp4bit,"
                         "skip_first=2' — 'chunks=N' selects the chunked "
                         "ring-overlap transport, 'schedule=serial' its "
                         "hoisted stage order for A/B runs (default "
                         "pipelined; see docs/COMPRESSION.md)")
    add_policy_alias(ap)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default="/tmp/repro_train_ckpt",
                    help="checkpoint directory ('' for no checkpoints)")
    ap.add_argument("--resume", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="resume from the latest checkpoint in --ckpt "
                         "(--no-resume starts fresh)")
    ap.add_argument("--profile-dir", default=None, dest="profile_dir",
                    help="write a jax.profiler trace of --profile-steps "
                         "here, with the compiled step's HLO text "
                         "(step.hlo.txt) whose op_name metadata maps each "
                         "device op to its scope (taco/*, attn, mlp, head, "
                         "optim)")
    ap.add_argument("--profile-steps", default="5:4", dest="profile_steps",
                    type=_first_count, metavar="FIRST:COUNT",
                    help="steps to profile with --profile-dir (default 5:4)")
    return ap


def _first_count(text: str) -> tuple[int, int]:
    first, sep, count = text.partition(":")
    try:
        out = int(first), int(count)
    except ValueError:
        out = None
    if not sep or out is None or out[0] < 0 or out[1] < 1:
        raise argparse.ArgumentTypeError(
            f"expected FIRST:COUNT with FIRST >= 0 and COUNT >= 1, "
            f"got {text!r}")
    return out


def build_trainer(args, cfg=None) -> Trainer:
    """The Trainer ``main`` runs for parsed ``args``; ``cfg`` overrides the
    ``--arch``/``--smoke`` architecture (e.g. a depth-cut config)."""
    shape = tuple(int(x) for x in args.mesh.split(","))
    axes = ("pod", "data", "model")
    if args.sp > 1:
        if shape[1] % args.sp:
            raise SystemExit(f"--sp {args.sp} must divide the data axis "
                             f"size {shape[1]}")
        shape = (shape[0], shape[1] // args.sp, args.sp, shape[2])
        axes = ("pod", "data", SP_AXIS, "model")
    mesh = make_mesh(shape, axes)
    fsdp_axes, tp_axis, tp, fsdp = mesh_axis_info(mesh)
    sp_axis, sp = sp_axis_info(mesh)

    if cfg is None:
        cfg = get_config(args.arch)
        if args.smoke:
            cfg = smoke_config(cfg)
    plan = make_plan(cfg, tp, fsdp)
    model = Model(cfg, plan, fsdp_axes=fsdp_axes, tp_axis=tp_axis,
                  sp_axis=sp_axis)
    comm_plan = from_spec(resolve_comm_spec(args))
    ctx = ParallelCtx(tp_axis=tp_axis, fsdp_axes=fsdp_axes, plan=comm_plan,
                      sp_axis=sp_axis, sp_mode=args.sp_mode)

    seq = args.seq or (64 if args.smoke else 4096)
    if seq % sp:
        raise SystemExit(f"--seq {seq} must be divisible by --sp {sp}")
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                                  global_batch=args.batch), cfg)
    oc = OptConfig(lr_max=args.lr, lr_min=args.lr / 10,
                   warmup_steps=max(args.steps // 20, 5),
                   total_steps=args.steps)
    tc = TrainerConfig(total_steps=args.steps,
                       ckpt_every=max(args.steps // 4, 10),
                       log_every=10, ckpt_dir=args.ckpt or None,
                       profile_dir=args.profile_dir,
                       profile_steps=args.profile_steps)
    return Trainer(model, mesh, ctx, oc, tc, data)


def main():
    args = build_parser().parse_args()
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    enable_compile_cache()
    trainer = build_trainer(args)
    _, _, losses = trainer.run(resume=args.resume)
    print(f"{trainer.model.cfg.name}: loss {losses[0]:.4f} -> "
          f"{losses[-1]:.4f} ({len(losses)} steps, "
          f"comm_spec={trainer.comm_spec})")


if __name__ == "__main__":
    main()
