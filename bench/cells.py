"""Finding a cell's pieces by name.

``BENCHMARK.json`` names each workload's configuration and traffic; the
rest of a cell lives in files of its own, found by name:

* ``bench/configs/<file>``: the model configuration as it is run (its
  ``arch`` block) with its published source, and the name of its plain
  reference, ``"reference": "<module>"``, found as ``bench/<module>.py``
  (``refmodel`` where the key is absent; ``refmodel.py`` says what a
  reference provides);
* ``bench/traffic/<traffic>.json``: the parameters of the packed-document
  generator and the rows per step (sequence length and micro-batch);
* ``bench/cells/<workload>.json``: how the trainer runs the cell (the
  program's model name, mesh and comm spec) and the limits of the
  comparison that decides ``correct``.  A configuration
  names a deployment and a (configuration, traffic) pair appears once in
  ``BENCHMARK.json``, so a cell that differs from another only in its
  comm spec runs a configuration file of its own;
* ``bench/metrics/<metric>.py``: the reader of each per-layer metric.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    run: dict
    end_to_end: list
    per_layer: list
    reference: object    # the configuration's reference module

    @property
    def arch(self) -> dict:
        return self.config["arch"]

    @property
    def seq(self) -> int:
        return int(self.traffic["seq"])

    @property
    def batch(self) -> int:
        return int(self.traffic["batch"])

    @property
    def tp(self) -> int:
        """Chips per tensor-parallel group: the mesh's last (model) axis."""
        return int(self.run["mesh"].split(",")[-1])


def _read(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def reference(name: str, base: Path):
    """The reference module ``name``: ``<base>/<name>.py``, else a module
    of ``bench/`` (on the import path), imported once per process."""
    if not name.isidentifier():
        raise SystemExit(f"reference {name!r} is not a module name")
    if name in sys.modules:
        return sys.modules[name]
    path = base / f"{name}.py"
    if not path.is_file():
        return importlib.import_module(name)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load(workload: str, benchmark: Path | None = None) -> Cell:
    """The cell of ``workload``.  With ``benchmark`` (a test's own
    ``BENCHMARK.json``), files are found beside it, as ``bench/`` and the
    checkout's root hold them for the real one."""
    root, base = ROOT, BENCH
    if benchmark is not None:
        root = base = Path(benchmark).resolve().parent
    bench = _read(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    moves = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in moves)]
    config = _read(root / conf["file"])
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=_read(base / "traffic" / f"{w['traffic']}.json"),
                run=_read(base / "cells" / f"{workload}.json"),
                end_to_end=e2e, per_layer=per_layer,
                reference=reference(config.get("reference", "refmodel"),
                                    base))


def peaks(device_kind: str) -> dict:
    table = _read(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json ({sorted(table['devices'])})")
    return table["devices"][device_kind]
