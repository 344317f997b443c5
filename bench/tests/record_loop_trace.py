"""Record the four-chip trace that ``test_loopspans.py`` reduces (run on a
host with four TPU chips):

    python bench/tests/record_loop_trace.py <out_dir>

The test cell ``tiny.tp4.taco`` (``data/BENCHMARK.json``) runs through the
harness's own path: the program's ``Trainer.run`` with the benchmark's data
source, whose tracer records a few whole loop iterations after a short
window, as a ``--trace 1`` run does.  The trace holds the program's loop
spans (``train``, ``train/*``), the benchmark's spans and each chip's
program runs.  It writes the trace and the compiled step's HLO text.
"""
import gzip
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
DATA = os.path.join(BENCH, "tests", "data", "BENCHMARK.json")
WORKLOAD, SEED, SECONDS = "tiny.tp4.taco", 3_100_000_013, 2.0


def main(out: str, require_chip: bool = True):
    import cells
    import harness

    cell = cells.load(WORKLOAD, DATA)
    harness.devices_for(cell.chips, require_chip)
    trainer = harness.build_trainer(cell)
    hooks = harness.ProgramHooks(trainer, cell)
    rows, _ = harness.make_rows(cell, SEED)
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    tracer = harness.Tracer(os.path.join(out, "profile"))
    harness.run_program(trainer, hooks, cell, SEED, rows, SECONDS, tracer)
    with open(tracer.xplane(), "rb") as src, \
            gzip.open(os.path.join(out, "loop.xplane.pb.gz"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    shutil.rmtree(tracer.path)
    with gzip.open(os.path.join(out, "loop.hlo.txt.gz"), "wt") as f:
        f.write(harness.step_hlo(trainer, cell))


if __name__ == "__main__":
    main(sys.argv[1])
