"""The control fails where the program passes: at a test size on the CPU,
the reference one precision step below the configuration's stated
compute precision (float8 operands under bfloat16, the stated wire as it
is) is put in the program's place and must exceed at least one limit of
the cell, while the program, on the same seed, stays inside all of
them."""
import os

import pytest

import calibrate
import cells
import compare
import harness

DATA = os.path.join(os.path.dirname(__file__), "data", "BENCHMARK.json")


@pytest.mark.parametrize("workload", ["tiny.1dev.plain", "tiny.tp4.taco",
                                      "tiny.1dev.moe"])
def test_control_exceeds_a_limit_and_the_program_does_not(workload):
    seed = 3_000_000_023
    cell = cells.load(workload, DATA)
    devices = harness.devices_for(cell.chips, require_chip=False)
    trainer = harness.build_trainer(cell)
    hooks = harness.ProgramHooks(trainer, cell)
    rows, _ = harness.make_rows(cell, seed)
    fd, _ = harness.run_program(trainer, hooks, cell, seed, rows, 0.0)
    prog = hooks.program_readings()
    ref = harness.reference_readings(cell, hooks.shapes, seed, fd, devices)
    ctl = harness.reference_readings(
        cell, hooks.shapes, seed, fd, devices,
        calibrate.CONTROL[cell.config["precision"]["compute"]])
    lim = cell.run["limits"]
    sound = {k: v for k, (v, _) in compare.gaps(prog, ref).items()}
    control = {k: v for k, (v, _) in compare.gaps(ctl, ref).items()}
    assert all(sound[k] <= lim[k] for k in lim), sound
    assert any(control[k] > lim[k] for k in lim), control
