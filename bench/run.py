"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's pieces are found by name from ``BENCHMARK.json`` (see
``cells.py``).  The run exits non-zero, with no result line, where JAX
finds no TPU or fewer chips than the cell asks for.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
# libtpu logs under /tmp unless told otherwise; a run writes only inside
# its checkout and the directories it is given
os.environ.setdefault("TPU_LOG_DIR", "disabled")

if __name__ == "__main__":
    import harness
    harness.main()
