"""The chunked attention core: its recompute backward against autodiff of
a dense softmax, its forward against the pre-custom-VJP chunked forward
(kept below as the reference), and what the backward stores."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.models import analysis_mode
from repro.models import attention as attn
from repro.models.layers import COMPUTE_DTYPE


def _chunked_forward_reference(q, k, v, *, causal, window, q_offset=0,
                               kv_len=None, q_chunk=512, kv_chunk=512):
    """The chunked core's forward as it stood before the custom VJP."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    q_chunk = min(q_chunk, sq)
    nq = sq // q_chunk if sq % q_chunk == 0 else 1
    if sq % q_chunk != 0:
        q_chunk = sq

    def scan(q, k, v, mask_fn):
        n = k.shape[2] // min(kv_chunk, k.shape[2])
        ck = k.shape[2] // n
        qf = q.astype(jnp.float32) * (1.0 / np.sqrt(hd))
        ks = k.reshape(b, h, n, ck, hd).transpose(2, 0, 1, 3, 4)
        vs = v.reshape(b, h, n, ck, hd).transpose(2, 0, 1, 3, 4)

        def body(carry, inp):
            acc, m, l = carry
            kc, vc, j = inp
            s_ = jnp.einsum("bhqd,bhkd->bhqk", qf, kc.astype(jnp.float32))
            s_ = s_ + mask_fn(j * ck, ck)[None, None]
            m_new = jnp.maximum(m, jnp.max(s_, axis=-1))
            p_ = jnp.exp(s_ - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p_, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p_, vc.astype(jnp.float32))
            return (acc, m_new, l), None

        init = (jnp.zeros((b, h, q.shape[2], hd), jnp.float32),
                jnp.full((b, h, q.shape[2]), attn.NEG_INF, jnp.float32),
                jnp.zeros((b, h, q.shape[2]), jnp.float32))
        (acc, _, l), _ = jax.lax.scan(body, init, (ks, vs, jnp.arange(n)))
        return acc / jnp.maximum(l, 1e-30)[..., None]

    def one_q_chunk(qi):
        qc = jax.lax.dynamic_slice_in_dim(qt, qi * q_chunk, q_chunk, axis=2)
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)
        lo, kc, vc = 0, kt, vt
        if window is not None:
            w = min(window, sk)
            width = min(w + q_chunk, sk)
            lo = jnp.clip(q_pos[0] - w + 1, 0, sk - width)
            kc = jax.lax.dynamic_slice_in_dim(kt, lo, width, axis=2)
            vc = jax.lax.dynamic_slice_in_dim(vt, lo, width, axis=2)

        def mask_fn(kv_start, ck):
            kpos = lo + kv_start + jnp.arange(ck)
            m = jnp.zeros((q_chunk, ck), jnp.float32)
            if causal or window is not None:
                m = jnp.where(kpos[None, :] > q_pos[:, None], attn.NEG_INF, m)
            if window is not None:
                m = jnp.where(kpos[None, :] <= q_pos[:, None] - w,
                              attn.NEG_INF, m)
            if kv_len is not None:
                m = jnp.where(kpos[None, :] >= kv_len, attn.NEG_INF, m)
            return m

        return scan(qc, kc, vc, mask_fn)

    if nq == 1:
        out = one_q_chunk(0)
    else:
        outs = jax.lax.map(one_q_chunk, jnp.arange(nq))
        out = outs.transpose(1, 2, 0, 3, 4).reshape(b, h, sq, hd)
    return out.transpose(0, 2, 1, 3).astype(COMPUTE_DTYPE)


def _dense(q, k, v, *, causal, window, q_offset=0, kv_len=None, **_):
    """Plain softmax attention over the whole (Sq, Sk) score matrix."""
    sq, sk, hd = q.shape[1], k.shape[1], q.shape[3]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
    q_pos = q_offset + jnp.arange(sq)[:, None]
    k_pos = jnp.arange(sk)[None, :]
    ok = jnp.ones((sq, sk), bool)
    if causal or window is not None:
        ok &= k_pos <= q_pos
    if window is not None:
        ok &= k_pos > q_pos - window
    if kv_len is not None:
        ok &= k_pos < kv_len
    p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                     precision=jax.lax.Precision.HIGHEST)
    return out.astype(COMPUTE_DTYPE)


# (Sq, Sk, kv heads (GQA expanded to 4 query heads), core kwargs);
# q_chunk 32, kv_chunk 16: several chunks on both axes
CASES = {
    "causal": (128, 128, 4, dict(causal=True, window=None)),
    "window_one_chunk": (128, 128, 4, dict(causal=True, window=16)),
    "window_wider_than_chunk": (128, 128, 4, dict(causal=True, window=48)),
    "cross": (64, 96, 4, dict(causal=False, window=None)),
    "single_q_chunk": (80, 80, 4, dict(causal=True, window=None)),
    "gqa": (128, 128, 2, dict(causal=True, window=None)),
    "kv_len": (64, 128, 4, dict(causal=True, window=None, q_offset=64,
                                kv_len=100)),
}


def _inputs(sq, sk, n_kv, seed=0):
    rng = np.random.default_rng(seed)
    b, h, hd = 2, 4, 16
    q = rng.normal(size=(b, sq, h, hd))
    k, v = (rng.normal(size=(b, sk, n_kv, hd)) for _ in range(2))
    w = rng.normal(size=(b, sq, h, hd))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, w))


def _expand(k, h):
    """_expand_kv's sharded-mode group expansion to ``h`` query heads."""
    plan = types.SimpleNamespace(kv_mode="sharded",
                                 group_size=h // k.shape[2])
    return attn._expand_kv(k, plan, None, None)


@pytest.mark.parametrize("case", sorted(CASES))
def test_recompute_backward_matches_dense_autodiff(case):
    """q, k, v cotangents of the chunked core (recompute backward) equal
    autodiff through a dense softmax, in f32; the forward is bit-identical
    to the chunked forward before the custom VJP."""
    sq, sk, n_kv, kw = CASES[case]
    q, k, v, w = _inputs(sq, sk, n_kv)
    h = q.shape[2]

    def loss(core):
        def f(q, k, v):
            out = core(q, _expand(k, h), _expand(v, h), **kw)
            return jnp.sum(out.astype(jnp.float32) * w)
        return f

    core = lambda *a, **k: attn.attention_core(  # noqa: E731
        *a, q_chunk=32, kv_chunk=16, **k)
    got = jax.jit(jax.grad(loss(core), argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(loss(_dense), argnums=(0, 1, 2)))(q, k, v)
    for name, g, r in zip("qkv", got, want):
        scale = float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=2e-5 * scale, rtol=0, err_msg=name)

    ke, ve = _expand(k, h), _expand(v, h)
    out = jax.jit(lambda *a: core(*a, **kw))(q, ke, ve)
    ref = jax.jit(lambda *a: _chunked_forward_reference(
        *a, q_chunk=32, kv_chunk=16, **kw))(q, ke, ve)
    np.testing.assert_array_equal(np.asarray(out.astype(jnp.float32)),
                                  np.asarray(ref.astype(jnp.float32)))


def _array_sizes(jaxpr):
    """Element counts of every array in ``jaxpr``, nested bodies included."""
    for eqn in jaxpr.eqns:
        for var in (*eqn.invars, *eqn.outvars):
            shape = getattr(getattr(var, "aval", None), "shape", None)
            if shape is not None:
                yield int(np.prod(shape))
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                if isinstance(sub, ClosedJaxpr):
                    yield from _array_sizes(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from _array_sizes(sub)


def test_backward_stores_no_score_sized_buffer():
    """Under full rematerialisation the backward holds nothing near one
    (H, Sq, Sk) score matrix (autodiff through the chunk scan stacks two
    of them), and the trace counter says which backward each trace took."""
    b, s, h, hd = 1, 2048, 2, 16
    x = jax.ShapeDtypeStruct((b, s, h, hd), jnp.float32)

    def grad():   # a fresh function each time: no cached trace
        core = jax.checkpoint(
            lambda q, k, v: attn.attention_core(q, k, v, causal=True,
                                                window=None),
            policy=jax.checkpoint_policies.nothing_saveable)
        return jax.grad(lambda q, k, v: jnp.sum(core(q, k, v).astype(
            jnp.float32)), argnums=(0, 1, 2))

    before = dict(attn.BACKWARD_TRACES)
    jaxpr = jax.make_jaxpr(grad())(x, x, x)
    assert max(_array_sizes(jaxpr.jaxpr)) < h * s * s // 2
    assert attn.BACKWARD_TRACES["recompute"] > before.get("recompute", 0)
    assert attn.BACKWARD_TRACES["autodiff"] == before.get("autodiff", 0)

    with analysis_mode.enabled():
        jax.make_jaxpr(grad())(x, x, x)
    assert attn.BACKWARD_TRACES["autodiff"] > before.get("autodiff", 0)


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "gpt-350m"])
def test_train_step_takes_recompute_backward(arch):
    """Every attention_core trace in a smoke model's train step takes the
    recompute backward, and the compiled step names the core's scope."""
    import re
    from repro.configs import get_config, make_plan, smoke_config
    from repro.core.parallel import ParallelCtx
    from repro.core.registry import from_spec
    from repro.core.telemetry import SCOPE_ATTN_CORE
    from repro.models.model import Model
    from repro.optim import adamw
    from repro.train.train_step import build_train_step

    cfg = smoke_config(get_config(arch))
    model = Model(cfg, make_plan(cfg, 1, 1))
    mesh = jax.make_mesh((1, 1, 1), ("pod", "data", "model"))
    step = build_train_step(model, mesh, ParallelCtx(
        plan=from_spec("baseline")), adamw.OptConfig(total_steps=4))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(adamw.init_opt_state, params)
    tok = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    batch = {"tokens": tok, "labels": tok,
             "mask": jax.ShapeDtypeStruct((2, 64), jnp.float32)}

    before = dict(attn.BACKWARD_TRACES)
    hlo = step.lower(params, opt, batch).compile().as_text()
    assert attn.BACKWARD_TRACES["recompute"] > before.get("recompute", 0)
    assert attn.BACKWARD_TRACES["autodiff"] == before.get("autodiff", 0)
    pat = re.compile(r"(^|[/(])" + re.escape(SCOPE_ATTN_CORE) + r"([/)]|$)")
    assert any(pat.search(n) for n in re.findall(r'op_name="([^"]*)"', hlo))
