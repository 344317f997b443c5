"""Plain float32 reference of the benchmark's dense decoder models: the
loss, its gradient and the AdamW step, in straightforward ``jax.numpy``.

It imports nothing of the program.  It reads the configuration's ``arch``
block (widths, heads, norm, positions, MLP kind) and follows the
published architectures, with the program's parametrisation where the
two must agree for the same weights: norm gains are stored as an offset
from 1, the attention output projection has no bias, and GELU is the tanh
form.  Every matrix product runs at ``Precision.HIGHEST``; attention and
the cross-entropy are computed in blocks of query rows, and every layer
is recomputed in the backward pass, so that the reference fits one chip.

Tensor parallelism is part of what the configuration states (``Wire``):
over ``tp`` chips, each chip holds a contiguous slice of the heads, of
the MLP's hidden units and of the vocabulary.  A block's input reaches
every chip (a gather) and the chips' partial sums of its output are added
(a scatter), as are the embedding's partial lookups; in the backward pass
the chips' partial gradients of a gathered input are added, and a
scattered output's gradient reaches every chip.  Where the configuration
states a compressed wire (``tp_wire``), every tensor that crosses a
chip, each chip's partial sum and partial gradient on its own, is
rounded as the wire rounds it: blocks of ``block`` elements along the
feature dimension, rotated by the normalised Hadamard matrix, scaled by
their largest magnitude to 448, rounded to float8 (e4m3), and rotated
back.

Variants put the reference in the program's place with a known fault or
a lower precision (``Variant``): ``fp8`` rounds every matrix product's
operands to float8 (e4m3) with a per-tensor scale; ``no_exchange`` keeps,
of every scatter, only the first chip's partial sum (the exchange between
chips left out); ``double`` moves one leaf twice as far as the update
says.

A configuration names its reference: ``"reference": "<module>"`` in its
file, a module beside ``refmodel.py`` (for the benchmark's own tests,
beside their ``BENCHMARK.json``); without the key it is this module.  A
reference module provides

* ``shapes(arch, pos_rows) -> {leaf: shape}``: the leaves the program's
  parameters must map onto (``weights.program_name``), a stacked layer
  segment's leaves under ``layers/`` (the first segment) or
  ``layers<i>/`` (segment ``i``);
* ``train_step(p, m, v, t, batch, arch, opt, var, wire)``: one AdamW step
  in float32, returning ``(p, m, v, loss, clipped gradient)``;
* ``flops_per_token(arch, seq)``: the model FLOPs per trained token that
  ``mfu`` divides by (recomputed work left out);
* ``FAULT_LEAF``: the leaf that the ``double`` fault moves twice;

and, imported from here rather than copied, ``Variant``, ``SOUND``,
``Wire``, ``wire_of`` and ``leaf_norms``.  The blocks of this module
(``embed``, ``attention_block``, ``mlp_block``, ``head_loss``,
``update``, ``ein``, ``norm``, ``layer_leaves``) are there to be reused
by a reference whose layers differ in one part.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from weights import stacked

HIGHEST = jax.lax.Precision.HIGHEST
NEG_INF = -1e30
E4M3_MAX = 448.0
SCALE_FLOOR = 1e-30


@dataclasses.dataclass(frozen=True)
class Variant:
    fp8: bool = False          # control: float8 operands in every product
    no_exchange: bool = False  # keep the first chip's partial sum alone
    double: str | None = None  # leaf whose update is applied twice


SOUND = Variant()
FAULT_LEAF = "layers/mlp/w1"   # the leaf the ``double`` fault moves


@dataclasses.dataclass(frozen=True)
class Wire:
    """The tensor-parallel layout: ``tp`` chips, and the compressed wire's
    block (0: tensors cross exactly)."""
    tp: int = 1
    block: int = 0


def wire_of(config: dict, tp: int) -> Wire:
    """The wire that a configuration states, over ``tp`` chips."""
    w = config.get("precision", {}).get("tp_wire")
    if tp == 1 or w is None:
        return Wire(tp=tp)
    if (w["rotation"], w["format"], w["scale"]) != \
            ("hadamard", "float8_e4m3fn", "block_max"):
        raise ValueError(f"no reference for the wire {w}")
    block = int(w["block"])
    if config["arch"]["d_model"] % block:
        raise ValueError("the wire's blocks must tile the feature dimension")
    return Wire(tp=tp, block=block)


def shapes(arch: dict, pos_rows: int = 0) -> dict[str, tuple]:
    """Leaf name -> shape of the model's weights (stacked over layers)."""
    L, d, f = arch["n_layers"], arch["d_model"], arch["d_ff"]
    h, kv, hd = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    v = arch["vocab_size"]
    out = {"embed/table": (v, d), "final_norm/scale": (d,)}
    if not arch["tie_embeddings"]:
        out["head/table"] = (v, d)
    if arch["pos"] == "learned":
        out["pos_embed"] = (pos_rows, d)
    ln = arch["norm"] == "layernorm"
    if ln:
        out["final_norm/bias"] = (d,)
    for n in ("norm1", "norm2"):
        out[f"layers/{n}/scale"] = (L, d)
        if ln:
            out[f"layers/{n}/bias"] = (L, d)
    out.update({"layers/attn/wq": (L, d, h * hd),
                "layers/attn/wk": (L, d, kv * hd),
                "layers/attn/wv": (L, d, kv * hd),
                "layers/attn/wo": (L, h * hd, d)})
    if arch["qkv_bias"]:
        out.update({"layers/attn/bq": (L, h * hd),
                    "layers/attn/bk": (L, kv * hd),
                    "layers/attn/bv": (L, kv * hd)})
    out["layers/mlp/w1"] = (L, d, f)
    out["layers/mlp/w2"] = (L, f, d)
    if arch["mlp"] == "swiglu":
        out["layers/mlp/w3"] = (L, d, f)
    else:
        out["layers/mlp/b1"] = (L, f)
        out["layers/mlp/b2"] = (L, d)
    return out


# ---------------------------------------------------------------------------
# the tensor-parallel wire
# ---------------------------------------------------------------------------

def _rotation(block: int):
    """The normalised Hadamard matrix of order ``block`` (Sylvester)."""
    h = np.ones((1, 1))
    while h.shape[0] < block:
        h = np.block([[h, h], [h, -h]])
    return jnp.asarray(h / math.sqrt(block), jnp.float32)


def _round_wire(x, block: int):
    """``x`` as the receiving chip decodes it; blocks never straddle the
    last dimension, so leading dimensions (chips) stay apart."""
    if not block:
        return x
    h = _rotation(block)
    z = jnp.matmul(x.reshape(-1, block), h, precision=HIGHEST)
    s = jnp.maximum(jnp.max(jnp.abs(z), -1, keepdims=True) / E4M3_MAX,
                    SCALE_FLOOR)
    q = jnp.clip(z / s, -E4M3_MAX, E4M3_MAX).astype(jnp.float8_e4m3fn)
    return jnp.matmul(q.astype(jnp.float32) * s, h,
                      precision=HIGHEST).reshape(x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _gather(y, wire: Wire):
    """``y`` on every chip: ``(tp, *y.shape)``."""
    return jnp.broadcast_to(_round_wire(y, wire.block)[None],
                            (wire.tp,) + y.shape)


def _gather_fwd(y, wire):
    return _gather(y, wire), None


def _gather_bwd(wire, _, ct):
    return (jnp.sum(_round_wire(ct, wire.block), axis=0),)


_gather.defvjp(_gather_fwd, _gather_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _scatter(parts, wire: Wire):
    """The sum of the chips' partial sums ``parts`` ``(tp, ...)``."""
    return jnp.sum(_round_wire(parts, wire.block), axis=0)


def _scatter_fwd(parts, wire):
    return _scatter(parts, wire), None


def _scatter_bwd(wire, _, ct):
    return (jnp.broadcast_to(_round_wire(ct, wire.block)[None],
                             (wire.tp,) + ct.shape),)


_scatter.defvjp(_scatter_fwd, _scatter_bwd)


def _exchange(parts, wire: Wire, var: Variant):
    if var.no_exchange:
        parts = parts * (jnp.arange(wire.tp) == 0).astype(
            parts.dtype).reshape((-1,) + (1,) * (parts.ndim - 1))
    return _scatter(parts, wire)


def _cols(w, tp):
    """(d, c) -> (tp, d, c/tp): each chip's slice of the columns."""
    return w.reshape(w.shape[0], tp, -1).swapaxes(0, 1)


def _rows(w, tp):
    """(c, d) -> (tp, c/tp, d): each chip's slice of the rows."""
    return w.reshape(tp, -1, w.shape[-1])


def _vec(b, tp):
    """(c,) -> (tp, 1, 1, c/tp), broadcast over (batch, sequence)."""
    return b.reshape(tp, 1, 1, -1)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _qdq(x):
    """float8 e4m3 rounding with a per-tensor scale; gradient passes
    straight through."""
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    s = jnp.maximum(amax, SCALE_FLOOR) / E4M3_MAX
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def ein(spec, a, b, var: Variant):
    if var.fp8:
        a, b = _qdq(a), _qdq(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def norm(x, p, prefix, arch):
    eps = arch["norm_eps"]
    if arch["norm"] == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * (1.0 + p[f"{prefix}/scale"]) \
            + p[f"{prefix}/bias"]
    ms = jnp.mean(x * x, -1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * (1.0 + p[f"{prefix}/scale"])


def _rope(x, theta):
    """Rotary positions on (B, S, H, hd), halves rotated as pairs."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2) / hd))
    ang = np.arange(s)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, var: Variant, block: int):
    """Causal softmax attention, one block of query rows at a time."""
    b, s, h, hd = q.shape
    block = min(block, s)
    nb = s // block
    qs = q.reshape(b, nb, block, h, hd).swapaxes(0, 1)
    kpos = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        i, qc = args
        if var.fp8:
            qc, kk, vv = _qdq(qc), _qdq(k), _qdq(v)
        else:
            kk, vv = k, v
        sc = jnp.einsum("bqhd,bkhd->bhqk", qc, kk, precision=HIGHEST)
        sc = sc / math.sqrt(hd)
        qpos = i * block + jnp.arange(block)
        sc = jnp.where(kpos[None, :] > qpos[:, None], NEG_INF, sc)
        pr = jax.nn.softmax(sc, axis=-1)
        if var.fp8:
            pr = _qdq(pr)
        return jnp.einsum("bhqk,bkhd->bqhd", pr, vv, precision=HIGHEST)

    out = jax.lax.map(one, (jnp.arange(nb), qs))
    return out.swapaxes(0, 1).reshape(b, s, h * hd)


def _heads(x, n, hd):
    """Each chip's heads (tp, B, S, n/tp*hd) -> (B, S, n, hd), chip-major."""
    tp, b, s, _ = x.shape
    return jnp.moveaxis(x.reshape(tp, b, s, n // tp, hd), 0, 2).reshape(
        b, s, n, hd)


def attention_block(x, lp, arch, var: Variant, wire: Wire, block: int):
    """``x`` plus the layer's attention (``lp``: one layer's leaves)."""
    b, s, _ = x.shape
    tp = wire.tp
    h, kv, hd = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    y = _gather(norm(x, lp, "norm1", arch), wire)       # (tp, B, S, d)
    q = ein("tbsd,tdc->tbsc", y, _cols(lp["attn/wq"], tp), var)
    k = ein("tbsd,tdc->tbsc", y, _cols(lp["attn/wk"], tp), var)
    v = ein("tbsd,tdc->tbsc", y, _cols(lp["attn/wv"], tp), var)
    if arch["qkv_bias"]:
        q = q + _vec(lp["attn/bq"], tp)
        k = k + _vec(lp["attn/bk"], tp)
        v = v + _vec(lp["attn/bv"], tp)
    q, k, v = _heads(q, h, hd), _heads(k, kv, hd), _heads(v, kv, hd)
    if arch["pos"] == "rope":
        q, k = _rope(q, arch["rope_theta"]), _rope(k, arch["rope_theta"])
    group = h // kv                 # query head j reads kv head j // group
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    o = _attention(q, k, v, var, block)                 # (B, S, h*hd)
    o = jnp.moveaxis(o.reshape(b, s, tp, -1), 2, 0)     # each chip's heads
    return x + _exchange(ein("tbsc,tcd->tbsd", o, _rows(lp["attn/wo"], tp),
                             var), wire, var)


def mlp_block(x, lp, arch, var: Variant, wire: Wire):
    """``x`` plus the layer's dense MLP."""
    tp = wire.tp
    y = _gather(norm(x, lp, "norm2", arch), wire)
    if arch["mlp"] == "swiglu":
        a = jax.nn.silu(ein("tbsd,tdf->tbsf", y, _cols(lp["mlp/w1"], tp),
                            var)) \
            * ein("tbsd,tdf->tbsf", y, _cols(lp["mlp/w3"], tp), var)
    else:
        a = jax.nn.gelu(ein("tbsd,tdf->tbsf", y, _cols(lp["mlp/w1"], tp),
                            var) + _vec(lp["mlp/b1"], tp),
                        approximate=True)
    x = x + _exchange(ein("tbsf,tfd->tbsd", a, _rows(lp["mlp/w2"], tp),
                          var), wire, var)
    if arch["mlp"] != "swiglu":
        x = x + lp["mlp/b2"]
    return x


def embed(p: dict, tokens, arch: dict, var: Variant, wire: Wire):
    """The residual stream's input (B, S, d): token rows and positions."""
    tp = wire.tp
    if arch["n_heads"] % tp or arch["n_kv_heads"] % tp:
        raise ValueError("the heads do not split over the chips")
    table = p["embed/table"]
    # each chip's partial lookup: the rows of its slice of the vocabulary
    owner = tokens // (table.shape[0] // tp)
    mine = owner[None] == jnp.arange(tp).reshape(tp, 1, 1)
    x = _exchange(jnp.where(mine[..., None], table[tokens][None], 0.0),
                  wire, var)
    if arch["pos"] == "learned":
        x = x + p["pos_embed"][:tokens.shape[1]][None]
    return x


def layer_leaves(p: dict, prefix: str = "layers/") -> dict:
    """The stacked leaves under ``prefix``, named within one layer."""
    return {k[len(prefix):]: w for k, w in p.items() if k.startswith(prefix)}


def head_loss(x, p: dict, batch: dict, arch: dict, var: Variant,
              wire: Wire, block: int):
    """Mean next-token cross-entropy over the mask of the last layer's
    output ``x``: final norm, output head, in blocks of rows."""
    labels, mask = batch["labels"], batch["mask"]
    b, s = labels.shape
    tp = wire.tp
    x = _gather(norm(x, p, "final_norm", arch), wire)  # (tp, B, S, d)
    head = p["embed/table"] if arch["tie_embeddings"] else p["head/table"]
    head = head.reshape(tp, -1, head.shape[-1])         # each chip's vocab

    rows = min(block, s)
    xs = jnp.moveaxis(x.reshape(tp, b, s // rows, rows, -1), 2, 0)
    ys = labels.reshape(b, s // rows, rows).swapaxes(0, 1)
    ms = mask.reshape(b, s // rows, rows).swapaxes(0, 1)

    @jax.checkpoint
    def xent(carry, inp):
        xc, yc, mc = inp
        logits = ein("tbrd,tvd->brtv", xc, head, var)
        logits = logits.reshape(logits.shape[:2] + (-1,))
        nll = jax.nn.logsumexp(logits, -1) \
            - jnp.take_along_axis(logits, yc[..., None], -1)[..., 0]
        return carry + jnp.sum(nll * mc), None

    total, _ = jax.lax.scan(xent, jnp.zeros((), jnp.float32), (xs, ys, ms))
    return total / jnp.maximum(jnp.sum(mask), 1.0)


def loss(p: dict, batch: dict, arch: dict, var: Variant = SOUND,
         wire: Wire = Wire(), block: int = 512):
    """Mean next-token cross-entropy over the mask."""
    x = embed(p, batch["tokens"], arch, var, wire)

    @jax.checkpoint
    def body(x, lp):
        x = attention_block(x, lp, arch, var, wire, block)
        return mlp_block(x, lp, arch, var, wire), None

    x, _ = jax.lax.scan(body, x, layer_leaves(p))
    return head_loss(x, p, batch, arch, var, wire, block)


def flops_per_token(arch: dict, seq: int) -> float:
    """Model FLOPs per token, 6*N + 12*n_layers*(n_heads*head_dim)*seq
    (PaLM, appendix B): N counts the weight-matrix parameters a token
    multiplies, output head included and the embedding lookup left out."""
    d, f, v = arch["d_model"], arch["d_ff"], arch["vocab_size"]
    q = arch["n_heads"] * arch["head_dim"]
    kv = arch["n_kv_heads"] * arch["head_dim"]
    mlp = (3 if arch["mlp"] == "swiglu" else 2) * d * f
    matrix_params = arch["n_layers"] * (2 * d * q + 2 * d * kv + mlp) + v * d
    attn = 12 * arch["n_layers"] * arch["n_heads"] * arch["head_dim"] * seq
    return 6.0 * matrix_params + attn


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------

def learning_rate(t, opt: dict):
    """Linear warm-up to ``lr_max``, then cosine decay to ``lr_min``;
    ``t`` counts updates from 1."""
    t = t.astype(jnp.float32)
    w, total = opt["warmup_steps"], opt["total_steps"]
    warm = opt["lr_max"] * t / max(w, 1)
    frac = jnp.clip((t - w) / max(total - w, 1), 0.0, 1.0)
    cos = opt["lr_min"] + 0.5 * (opt["lr_max"] - opt["lr_min"]) \
        * (1.0 + jnp.cos(jnp.pi * frac))
    return jnp.where(t < w, warm, cos)


def leaf_norms(tree: dict) -> dict:
    """Per-leaf L2 norms; a stacked layer leaf gives one per layer."""
    out = {}
    for k, a in tree.items():
        if stacked(k):
            out[k] = jnp.sqrt(jnp.sum(a * a, axis=tuple(range(1, a.ndim))))
        else:
            out[k] = jnp.sqrt(jnp.sum(a * a))
    return out


def update(p, m, v, t, g, opt: dict, var: Variant = SOUND):
    """AdamW at update count ``t`` (0 for the first) with the gradient
    ``g`` clipped to ``clip_norm``.  Returns the new (p, m, v) and the
    clipped gradient."""
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
    clip = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-12))
    g = {k: x * clip for k, x in g.items()}
    t1 = t + 1
    lr = learning_rate(t1, opt)
    b1, b2 = opt["b1"], opt["b2"]
    bc1 = 1.0 - b1 ** t1.astype(jnp.float32)
    bc2 = 1.0 - b2 ** t1.astype(jnp.float32)
    np_, nm, nv = {}, {}, {}
    for k in p:
        nm[k] = b1 * m[k] + (1 - b1) * g[k]
        nv[k] = b2 * v[k] + (1 - b2) * g[k] * g[k]
        upd = (nm[k] / bc1) / (jnp.sqrt(nv[k] / bc2) + opt["eps"])
        step = lr * (upd + opt["weight_decay"] * p[k])
        np_[k] = p[k] - (2.0 * step if k == var.double else step)
    return np_, nm, nv, g


def train_step(p, m, v, t, batch, arch: dict, opt: dict,
               var: Variant = SOUND, wire: Wire = Wire()):
    """One AdamW step at update count ``t`` (0 for the first).  Returns
    the new (p, m, v), the loss, and the clipped gradient the update
    used (call under jit, reducing it there)."""
    lval, g = jax.value_and_grad(loss)(p, batch, arch, var, wire)
    p, m, v, g = update(p, m, v, t, g, opt, var)
    return p, m, v, lval, g
