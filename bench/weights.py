"""Weights made by the benchmark from the seed, for the program and for the
reference alike.

Every leaf is named as the reference names it (``layers/attn/wq`` for a
stack of per-layer matrices, ``layers1/...`` for a second segment of
layers of another kind, ``embed/table``, ...) and drawn from its own
key, ``fold_in(seed key, index of the name in sorted order)``, as
N(0, 0.02) (learned positions N(0, 0.01)) and rounded to bfloat16, the
type the program trains in.  Norm gains and biases are drawn too, so that
every term of the model moves the loss.  The program gets these values
as bfloat16 in its own tree and shardings, in one jitted call; the
reference gets the same values widened to float32.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp

from feed import seed_words

SCALE = 0.02
POS_SCALE = 0.01
SEGMENT = re.compile(r"segments/(\d+)/")
STACKED = re.compile(r"layers\d*/")


def program_name(path) -> str:
    """Reference name of a leaf of the program's parameter tree: the
    stacked layer segment ``segments/0/...`` is ``layers/...``, and a
    later segment ``segments/<i>/...`` is ``layers<i>/...``."""
    parts = []
    for k in path:
        parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
    name = "/".join(parts)
    seg = SEGMENT.match(name)
    if seg:
        i = seg.group(1)
        return f"layers{'' if i == '0' else i}/" + name[seg.end():]
    return name


def stacked(name: str) -> bool:
    """Whether leaf ``name`` stacks one layer per row of its first axis."""
    return STACKED.match(name) is not None


def base_key(seed: int):
    a, b = seed_words(seed)
    return jax.random.fold_in(jax.random.PRNGKey(a), b)


def draw(key, names: list[str], name: str, shape) -> jax.Array:
    """The bfloat16 value of leaf ``name``."""
    k = jax.random.fold_in(key, sorted(names).index(name))
    scale = POS_SCALE if name == "pos_embed" else SCALE
    return (jax.random.normal(k, shape, jnp.float32) * scale
            ).astype(jnp.bfloat16)


def reference_weights(key, shapes: dict[str, tuple]) -> dict:
    """``name -> float32`` weights (call under jit)."""
    names = list(shapes)
    return {n: draw(key, names, n, s).astype(jnp.float32)
            for n, s in shapes.items()}
