"""Plain float32 reference of a sparse-expert decoder on one chip: the
dense decoder's attention, embedding, head and AdamW (``refmodel``), with
each layer's MLP replaced by routed experts.

The expert layer, as the configuration's ``moe`` block states it: a
router (``d_model`` to ``n_experts`` logits, softmax), the ``top_k``
experts of each token with their probabilities renormalised to sum to 1,
and each expert a SwiGLU MLP of width ``d_ff``.  Every expert runs on
every token and the unselected ones are weighted by 0, so no token is
dropped: the configuration sets ``capacity_factor`` to ``n_experts /
top_k``, at which the program drops none either.

The loss that is differentiated adds the program's load-balancing term,
as the program defines it (``models/moe.py``, ``train/train_step.py``):
0.01 times the sum over layers of ``n_experts * sum_e density_e *
mean_prob_e``, where, over each group of up to 4096 tokens (all tokens
where 4096 does not divide them), ``density_e`` is the share of tokens
whose first choice is expert ``e`` (no gradient) and ``mean_prob_e`` the
mean router probability of ``e``, and a layer's term is the mean over
its groups.  The loss reported is the cross-entropy alone, as the
program reports it.

Written for one chip: a configuration whose experts are split over
several needs their tensor-parallel layout here first.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import refmodel
from refmodel import SOUND, Variant, Wire, ein, leaf_norms, norm, wire_of  # noqa: F401

FAULT_LEAF = "layers/moe/w1"
AUX_WEIGHT = 0.01
GROUP = 4096


def shapes(arch: dict, pos_rows: int = 0) -> dict[str, tuple]:
    """The dense decoder's leaves with the MLP's replaced by the experts'."""
    L, d, f = arch["n_layers"], arch["d_model"], arch["d_ff"]
    e = arch["moe"]["n_experts"]
    out = {k: s for k, s in refmodel.shapes(arch, pos_rows).items()
           if not k.startswith("layers/mlp/")}
    out.update({"layers/moe/router": (L, d, e),
                "layers/moe/w1": (L, e, d, f),
                "layers/moe/w3": (L, e, d, f),
                "layers/moe/w2": (L, e, f, d)})
    return out


def _balance(probs, top_e, e: int):
    """The load-balancing term of one layer, over groups of tokens."""
    t = probs.shape[0]
    group = min(GROUP, t)
    if t % group:
        group = t
    probs = probs.reshape(t // group, group, e)
    first = jax.nn.one_hot(top_e[:, 0].reshape(t // group, group), e)
    density = jnp.mean(first, axis=1)
    return jnp.mean(e * jnp.sum(density * jnp.mean(probs, axis=1), -1))


def moe_block(x, lp, arch: dict, var: Variant):
    """``x`` plus the layer's expert output, and its load-balancing term."""
    b, s, d = x.shape
    e, k = arch["moe"]["n_experts"], arch["moe"]["top_k"]
    y = norm(x, lp, "norm2", arch)
    logits = ein("bsd,de->bse", y, lp["moe/router"], var)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)
    gate = jnp.sum(jax.nn.one_hot(top_e, e) * top_p[..., None], axis=-2)
    h = ein("bsd,edf->bsef", y, lp["moe/w1"], var)
    g = ein("bsd,edf->bsef", y, lp["moe/w3"], var)
    out = ein("bsef,efd->bsed", jax.nn.silu(h) * g, lp["moe/w2"], var)
    x = x + jnp.einsum("bsed,bse->bsd", out, gate,
                       precision=refmodel.HIGHEST)
    aux = _balance(probs.reshape(b * s, e), top_e.reshape(b * s, k), e)
    return x, aux


def loss(p: dict, batch: dict, arch: dict, var: Variant = SOUND,
         wire: Wire = Wire(), block: int = 512):
    """(cross-entropy plus the load-balancing term, cross-entropy)."""
    if wire.tp != 1:
        raise ValueError("this reference runs the experts on one chip")
    if arch["mlp"] != "swiglu":
        raise ValueError("this reference's experts are SwiGLU MLPs")
    x = refmodel.embed(p, batch["tokens"], arch, var, wire)

    @jax.checkpoint
    def body(carry, lp):
        x, aux = carry
        x = refmodel.attention_block(x, lp, arch, var, wire, block)
        x, a = moe_block(x, lp, arch, var)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               refmodel.layer_leaves(p))
    xent = refmodel.head_loss(x, p, batch, arch, var, wire, block)
    return xent + AUX_WEIGHT * aux, xent


def train_step(p, m, v, t, batch, arch: dict, opt: dict,
               var: Variant = SOUND, wire: Wire = Wire()):
    """One AdamW step; returns (p, m, v, cross-entropy, clipped gradient)."""
    (_, xent), g = jax.value_and_grad(loss, has_aux=True)(
        p, batch, arch, var, wire)
    p, m, v, g = refmodel.update(p, m, v, t, g, opt, var)
    return p, m, v, xent, g


def flops_per_token(arch: dict, seq: int) -> float:
    """6*N + 12*n_layers*(n_heads*head_dim)*seq, N counting the matrices a
    token multiplies: attention, the router, its ``top_k`` experts' three
    and the output head."""
    d, f, v = arch["d_model"], arch["d_ff"], arch["vocab_size"]
    e, k = arch["moe"]["n_experts"], arch["moe"]["top_k"]
    q = arch["n_heads"] * arch["head_dim"]
    kv = arch["n_kv_heads"] * arch["head_dim"]
    layer = 2 * d * q + 2 * d * kv + d * e + k * 3 * d * f
    attn = 12 * arch["n_layers"] * arch["n_heads"] * arch["head_dim"] * seq
    return 6.0 * (arch["n_layers"] * layer + v * d) + attn
