"""End-to-end training tests: loss decreases, TACO-compressed training
tracks the baseline (the paper's Table 1 claim at CPU scale), checkpoint
restart resumes identically."""
import logging

import jax
import numpy as np
import pytest

from repro.configs import get_config, make_plan, smoke_config
from repro.core.parallel import ParallelCtx
from repro.core.registry import from_spec
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.optim.adamw import OptConfig
from repro.train.trainer import Trainer, TrainerConfig
from repro.runtime.fault_tolerance import FailureInjector

MESH = None


def mesh1():
    global MESH
    if MESH is None:
        MESH = jax.make_mesh((1, 1, 1), ("pod", "data", "model"))
    return MESH


def small_setup(tmp_path, comm_spec, total_steps=30, seed=0,
                arch="gpt-350m"):
    from repro.models.model import Model
    cfg = smoke_config(get_config(arch))
    plan = make_plan(cfg, 1, 1)
    model = Model(cfg, plan)
    ctx = ParallelCtx(plan=from_spec(comm_spec))
    oc = OptConfig(lr_max=1e-3, lr_min=1e-4, warmup_steps=5,
                   total_steps=total_steps)
    tc = TrainerConfig(total_steps=total_steps, ckpt_every=10,
                       ckpt_dir=str(tmp_path / "ckpt"), seed=seed)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=64,
                                  global_batch=8), cfg)
    return model, ctx, oc, tc, data


def test_loss_decreases(tmp_path):
    model, ctx, oc, tc, data = small_setup(
        tmp_path, "baseline", total_steps=30)
    tr = Trainer(model, mesh1(), ctx, oc, tc, data)
    _, _, losses = tr.run(resume=False)
    first, last = np.mean(losses[:5]), np.mean(losses[-5:])
    assert last < first - 0.3, (first, last)


def test_taco_training_tracks_baseline(tmp_path):
    """The paper's core accuracy claim (Table 1) at smoke scale: full TACO
    compression on every TP site changes the loss trajectory only
    marginally."""
    runs = {}
    for name, spec in [
        ("base", "baseline"),
        ("taco", "tp=taco:jnp"),
    ]:
        model, ctx, oc, tc, data = small_setup(
            tmp_path / name, spec, total_steps=30)
        tr = Trainer(model, mesh1(), ctx, oc, tc, data)
        _, _, losses = tr.run(resume=False)
        runs[name] = losses
    final_base = np.mean(runs["base"][-5:])
    final_taco = np.mean(runs["taco"][-5:])
    # paper: +0.25% val-loss degradation; allow 2% at this tiny scale
    assert abs(final_taco - final_base) / final_base < 0.02, \
        (final_base, final_taco)
    assert final_taco < np.mean(runs["taco"][:5]) - 0.3  # it actually learns


def test_restart_after_injected_failure(tmp_path):
    """Kill the run mid-flight; the trainer must restore the latest
    checkpoint and converge to the same final state as an uninterrupted
    run (bitwise replay thanks to the pure-function-of-step pipeline)."""
    model, ctx, oc, tc, data = small_setup(
        tmp_path, "baseline", total_steps=20)
    # uninterrupted reference
    tr_ref = Trainer(model, mesh1(), ctx, oc, tc, data)
    p_ref, _, _ = tr_ref.run(resume=False)

    import shutil
    shutil.rmtree(tc.ckpt_dir, ignore_errors=True)
    tr = Trainer(model, mesh1(), ctx, oc, tc, data,
                 injector=FailureInjector(fail_at_steps=[13]))
    p_failed, _, _ = tr.run(resume=False)

    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_failed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_failure_before_first_step_is_not_retried(tmp_path):
    """A failure before any step completed (compile error, device OOM)
    surfaces at once: rebuilding cannot fix it, so nothing is retried."""
    model, ctx, oc, tc, data = small_setup(tmp_path, "baseline",
                                           total_steps=3)
    injector = FailureInjector(fail_at_steps=[0])
    tr = Trainer(model, mesh1(), ctx, oc, tc, data, injector=injector)
    with pytest.raises(RuntimeError, match="injected node failure"):
        tr.run(resume=False)
    assert tr.losses == [] and injector.fired == {0}


def test_init_state_is_sharded_and_matches_eager_init(tmp_path):
    """init_state builds params and optimizer state under jit straight
    into their target shardings, with the values an eager init gives (up
    to one bf16 rounding step where jit fuses the init scale into the
    cast), and an f32 master copy equal to the params."""
    from jax.sharding import NamedSharding
    model, ctx, oc, tc, data = small_setup(tmp_path, "baseline")
    tr = Trainer(model, mesh1(), ctx, oc, tc, data)
    params, opt, step = tr.init_state()
    assert step == 0
    eager = model.init(jax.random.PRNGKey(tc.seed))
    specs = model.partition_specs()
    for got, want, spec in zip(jax.tree.leaves(params),
                               jax.tree.leaves(eager),
                               jax.tree.leaves(specs)):
        assert got.sharding == NamedSharding(mesh1(), spec)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2.0 ** -7, atol=0)
    for got, want in zip(jax.tree.leaves(opt["master"]),
                         jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(want, np.float32))


def test_no_checkpoint_dir_writes_nothing(tmp_path):
    model, ctx, oc, tc, data = small_setup(tmp_path, "baseline",
                                           total_steps=2)
    import dataclasses
    tc = dataclasses.replace(tc, ckpt_dir=None)
    tr = Trainer(model, mesh1(), ctx, oc, tc, data)
    _, _, losses = tr.run(resume=True)
    assert len(losses) == 2 and list(tmp_path.iterdir()) == []


# --------------------------------------------------------------------------
# the loop's spans and the step's scopes (--profile-dir)
# --------------------------------------------------------------------------

PHASES = ["train/data", "train/place", "train/dispatch", "train/sync",
          "train/log"]


def _loop_spans(profile_dir):
    """``{step_num: (start, end, [(start, phase), ...])}`` from the
    trace's host plane."""
    import glob
    from jax.profiler import ProfileData
    path, = glob.glob(f"{profile_dir}/plugins/profile/*/*.xplane.pb")
    steps, phases = {}, []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                end = ev.start_ns + ev.duration_ns
                if ev.name == "train":
                    steps[dict(ev.stats)["step_num"]] = (ev.start_ns, end, [])
                elif ev.name.startswith("train/"):
                    phases.append((ev.start_ns, end, ev.name))
    for s, e, name in sorted(phases):
        for lo, hi, inside in steps.values():
            if lo <= s and e <= hi:
                inside.append(name)
    return steps


@pytest.mark.parametrize("spec", ["taco", "baseline"])
def test_profiled_steps_carry_loop_spans_and_step_scopes(tmp_path, spec):
    """``profile_dir`` traces the chosen steps: each is one ``train``
    span with its step number holding the host phases in loop order; the
    compiled step's HLO text beside the trace carries every named scope
    in its op_name metadata (no ``taco/`` scope without compression)."""
    import re
    from repro.core import telemetry
    model, ctx, oc, tc, data = small_setup(tmp_path, spec, total_steps=4)
    tc.profile_dir = str(tmp_path / "profile")
    tc.profile_steps = (1, 3)
    tr = Trainer(model, mesh1(), ctx, oc, tc, data)
    tr.run(resume=False)
    steps = _loop_spans(tc.profile_dir)
    assert sorted(steps) == [1, 2, 3]
    for n, (_, _, inside) in steps.items():
        # step 3 is the last: its checkpoint is saved inside the span
        assert inside == PHASES + (["train/ckpt"] if n == 3 else []), n

    with open(tmp_path / "profile" / telemetry.StepProfile.HLO_FILE) as f:
        names = set(re.findall(r'op_name="([^"]*)"', f.read()))

    def has(scope):
        pat = re.compile(r"(^|[/(])" + re.escape(scope) + r"([/)]|$)")
        return any(pat.search(n) for n in names)

    for scope in telemetry.STEP_SCOPES:
        assert has(scope) == (spec == "taco" or not scope.startswith(
            "taco/")), scope
