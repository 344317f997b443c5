"""A whole run of a test-sized cell, past the harness's look for a chip,
with the timed path sound and then broken underneath: ``correct`` must
come out true for the sound program and false for every fault the cell
can have.

The faults are planted in the program at run time, never in its files:

* ``state_unchanged``: the optimizer returns its state as it came;
* ``half_batch``: half of the batch (rows, or tokens where the batch is
  one row) left out, the mean taken over the rest;
* ``answer_altered``: one leaf's update applied twice: the program's leaf
  that the cell's reference names ``FAULT_LEAF`` (``segments/0/mlp/w1``
  in a dense cell, ``segments/0/moe/w1`` in the sparse-expert one);
* ``no_exchange``: the tensor-parallel reduce-scatter takes the local
  slice of its own partial sum (cells of several chips only).

The test cells (``data/``) keep the real cells' shapes of model and path
at a size the CPU holds, with limits of their own set from CPU readings
in the same way (``data/cells/*.json``).
"""
import os

import jax
import jax.numpy as jnp
import pytest

import cells
import harness
import weights

DATA = os.path.join(os.path.dirname(__file__), "data", "BENCHMARK.json")
CELLS = ["tiny.1dev.plain", "tiny.tp4.taco", "tiny.1dev.moe"]
SEED = 3_000_000_019


def run(workload):
    return harness.main(["--workload", workload, "--seed", str(SEED),
                         "--seconds", "0.3", "--trace", "0"],
                        require_chip=False, benchmark=DATA)


def _at(leaf, fn, *trees):
    """The last tree with ``fn`` applied to the leaf named ``leaf``."""
    return jax.tree_util.tree_map_with_path(
        lambda path, *a: fn(*a) if weights.program_name(path) == leaf
        else a[-1], *trees)


def plant(fault, monkeypatch, leaf=None):
    from repro.core import collectives
    from repro.models import transformer
    from repro.optim import adamw

    if fault in ("state_unchanged", "answer_altered"):
        orig = adamw.adamw_update

        def update(grads, opt_state, oc, model):
            params, state, metrics = orig(grads, opt_state, oc, model)
            if fault == "state_unchanged":
                old = jax.tree_util.tree_map(
                    lambda m: m.astype(jnp.bfloat16), opt_state["master"])
                return old, opt_state, metrics
            master = _at(leaf, lambda was, now: was + 2.0 * (now - was),
                         opt_state["master"], state["master"])
            params = _at(leaf, lambda m, p: m.astype(p.dtype), master,
                         params)
            return params, dict(state, master=master), metrics

        monkeypatch.setattr(adamw, "adamw_update", update)
    elif fault == "half_batch":
        orig = transformer.forward_train

        def forward(params, batch, cfg, plan, ctx):
            mask = batch["mask"]
            if mask.shape[0] > 1:
                keep = jnp.arange(mask.shape[0]) < mask.shape[0] // 2
                mask = mask * keep[:, None]
            else:
                keep = jnp.arange(mask.shape[1]) < mask.shape[1] // 2
                mask = mask * keep[None, :]
            return orig(params, dict(batch, mask=mask), cfg, plan, ctx)

        monkeypatch.setattr(transformer, "forward_train", forward)
    elif fault == "no_exchange":
        def scatter(x, axis, dim, fwd, bwd):
            n = x.shape[dim] // jax.lax.psum(1, axis)
            i = jax.lax.axis_index(axis)
            return jax.lax.dynamic_slice_in_dim(x, i * n, n, axis=dim)

        monkeypatch.setattr(collectives, "psum_scatter_c", scatter)
    else:
        raise ValueError(fault)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_program_is_correct(workload):
    out = run(workload)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("workload,fault", [
    (w, f) for w in CELLS
    for f in ("state_unchanged", "half_batch", "answer_altered")]
    + [("tiny.tp4.taco", "no_exchange")])
def test_fault_makes_the_run_incorrect(workload, fault, monkeypatch):
    monkeypatch.syspath_prepend(str(harness.cells.ROOT / "src"))
    plant(fault, monkeypatch,
          cells.load(workload, DATA).reference.FAULT_LEAF)
    out = run(workload)
    assert not out["correct"], (fault, out["checks"])
