"""Readings that set a cell's limits: the program, the control and the
faults, at the cell's own size, on many seeds in one process.

    python bench/calibrate.py --workload <name> --seeds 1,2,3 \\
        [--control-seeds 3] [--fault-seeds 3]

For each seed the program runs its untimed steps through the trainer,
exactly as in a benchmark run, and the float32 reference follows; the
gaps between them are the program's readings.  On the first
``--control-seeds`` seeds the control (the reference one precision step
below the configuration's stated compute precision: float8 operands
under bfloat16, with the configuration's wire as it is) takes the
program's place, and on the first
``--fault-seeds`` seeds each fault the cell can have does: half of every
row left out of the mean, one leaf (the reference's ``FAULT_LEAF``)
moved double, and on a cell of
several chips the exchange between them left out.  A state left
unchanged reads 1 by construction and is not run.  One JSON line per
seed.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import cells
import compare
import harness
import refmodel

CONTROL = {"bfloat16": refmodel.Variant(fp8=True)}   # one step below


def gaps(prog, ref) -> dict:
    return {k: v for k, (v, _) in compare.gaps(prog, ref).items()}


def main(argv=None, *, require_chip: bool = True, benchmark=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    cell = cells.load(args.workload, benchmark)
    devices = harness.devices_for(cell.chips, require_chip)
    harness.enable_cache()
    trainer = harness.build_trainer(cell)
    hooks = harness.ProgramHooks(trainer, cell)
    control = CONTROL[cell.config["precision"]["compute"]]
    variants = {"control": (control, False),
                "half_batch": (refmodel.SOUND, True),
                "leaf_double": (refmodel.Variant(
                    double=cell.reference.FAULT_LEAF), False)}
    if cell.tp > 1:
        variants["no_exchange"] = (refmodel.Variant(no_exchange=True), False)
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        rows, _ = harness.make_rows(cell, seed)
        hooks.trainer = trainer
        trainer.losses = []
        fd, _ = harness.run_program(trainer, hooks, cell, seed, rows, 0.0)
        prog = hooks.program_readings()
        ref = harness.reference_readings(cell, hooks.shapes, seed, fd,
                                         devices)
        line = {"seed": seed, "program": gaps(prog, ref)}
        for name, (var, half) in variants.items():
            n = args.control_seeds if name == "control" else args.fault_seeds
            if i < n:
                alt = harness.reference_readings(cell, hooks.shapes, seed,
                                                 fd, devices, var, half)
                line[name] = gaps(alt, ref)
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    sys.exit(main())
