"""The comparison that decides ``correct`` for a training cell.

Both sides give, for the first three steps of the same weights and rows:
the loss of each step, the per-leaf norms of the first gradient as the
optimizer used it (after clipping), and the per-leaf norms of the change
of the float32 parameters after the three updates.  A stacked layer
leaf (``layers/...``, or ``layers<i>/...`` for a later segment) counts
as one leaf per layer.  Three numbers come out:

* ``loss_gap``: the largest relative gap of a step's loss;
* ``grad_gap``: the worst leaf's gap between the two gradient norms,
  over the larger of the reference's norm of that leaf and of the
  median leaf;
* ``delta_gap``: the same for the parameter change, over the leaves that
  move: a leaf whose reference gradient is under a thousandth of the
  median leaf's (a key bias under softmax) moves under Adam by round-off
  alone and is left out.

Gaps of norms are blind to errors that leave a norm unchanged, as
rounding noise does, so a fourth number compares the first gradient
itself on a sample of its elements drawn from the seed (the same
positions on both sides):

* ``grad_err``: the worst moving leaf's norm of the difference of the two
  sampled gradients, over the reference's norm of its sample.

Under a compressed tensor-parallel wire the numbers above cannot tell a
program that multiplies in float8 from a sound one: each side rounds
every token's hop tensors to float8 on its own, the two sides' roundings
part ways after a few hops, and that noise is as large as float8
products'.
The gradient of the final norm's bias is the sum over every token of the
gradient at the last gather, before any other hop of the backward pass:
the sum averages the tokens' independent wire rounding away but keeps
the error that a float8 weight, shared by every token, adds to each.
Where the model has that leaf:

* ``final_bias_err``: the same relative error as ``grad_err``, of the
  final norm's bias.
"""
from __future__ import annotations

import math

import numpy as np

from weights import stacked

SAMPLE = 2048  # gradient elements sampled per leaf (per layer)

STILL = 1e-3   # a leaf under this share of the median gradient norm
FINAL_BIAS = "final_norm/bias"


def flat(norms: dict) -> dict[str, float]:
    """``name -> norm`` with stacked leaves split as ``name.<layer>``."""
    out = {}
    for k, v in norms.items():
        v = np.asarray(v, np.float64)
        if stacked(k):
            for i, x in enumerate(v):
                out[f"{k}.{i}"] = float(x)
        else:
            out[k] = float(v)
    return out


def worst_gap(got: dict, want: dict, leaves) -> tuple[float, str]:
    leaves = list(leaves)
    med = float(np.median([want[k] for k in leaves]))
    worst, where = 0.0, ""
    for k in leaves:
        gap = abs(got[k] - want[k]) / max(want[k], med, 1e-30)
        if not math.isfinite(gap):
            return math.inf, k
        if gap > worst:
            worst, where = gap, k
    return worst, where


def sample_index(shapes: dict, seed: int) -> dict:
    """Seeded element positions per leaf: ``(L, SAMPLE)`` for a stacked
    layer leaf (positions within one layer), ``(SAMPLE,)`` otherwise."""
    rng = np.random.default_rng([int(seed), 0x5A3])
    out = {}
    for k in sorted(shapes):
        s = shapes[k]
        if stacked(k):
            out[k] = rng.integers(0, int(np.prod(s[1:])), (s[0], SAMPLE),
                                  dtype=np.int32)
        else:
            out[k] = rng.integers(0, int(np.prod(s)), (SAMPLE,),
                                  dtype=np.int32)
    return out


def take_sample(tree: dict, idx: dict) -> dict:
    """The sampled elements of every leaf (call under jit)."""
    import jax.numpy as jnp
    out = {}
    for k, a in tree.items():
        if stacked(k):
            out[k] = jnp.take_along_axis(a.reshape(a.shape[0], -1), idx[k],
                                         axis=1)
        else:
            out[k] = a.reshape(-1)[idx[k]]
    return out


def sample_err(got: dict, want: dict, leaves) -> tuple[float, str]:
    worst, where = 0.0, ""
    for k in leaves:
        name, _, layer = k.partition(".")
        a = np.asarray(got[name], np.float64)
        b = np.asarray(want[name], np.float64)
        if layer:
            a, b = a[int(layer)], b[int(layer)]
        err = float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))
        if not math.isfinite(err):
            return math.inf, k
        if err > worst:
            worst, where = err, k
    return worst, where


def gaps(prog: dict, ref: dict) -> dict:
    """Readings ``{"loss": [..], "grad": {..}, "delta": {..}}`` of both
    sides -> ``{name: (value, where)}``."""
    lp, lr = np.asarray(prog["loss"], float), np.asarray(ref["loss"], float)
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    if not np.all(np.isfinite(lp)):
        loss_gap = math.inf
    gp, gr = flat(prog["grad"]), flat(ref["grad"])
    dp, dr = flat(prog["delta"]), flat(ref["delta"])
    if set(gp) != set(gr) or set(dp) != set(dr):
        raise ValueError("program and reference leaves differ: "
                         f"{sorted(set(gp) ^ set(gr))[:5]}")
    med_g = float(np.median(list(gr.values())))
    moving = [k for k in dr if gr[k] >= STILL * med_g]
    step = int(np.argmax(np.abs(lp - lr) / np.abs(lr)))
    out = {"loss_gap": (loss_gap, f"step {step}"),
           "grad_gap": worst_gap(gp, gr, gr),
           "delta_gap": worst_gap(dp, dr, moving),
           "grad_err": sample_err(prog["grad_sample"], ref["grad_sample"],
                                  moving)}
    if FINAL_BIAS in gr:
        out["final_bias_err"] = sample_err(prog["grad_sample"],
                                           ref["grad_sample"], [FINAL_BIAS])
    return out
