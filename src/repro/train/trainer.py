"""Training loop with checkpoint/restart, watchdog, and straggler logging.

The loop is deliberately restart-oriented: ALL state is (params, opt_state,
step); the data pipeline is pure-functional in step. ``Trainer.run`` can be
killed at any step and re-invoked — it resumes from the latest complete
checkpoint and replays identically (tested in tests/test_checkpoint.py).

Compression policy: the trainer delegates the CommPlan *schedule* to a
:class:`repro.core.policy.PolicyEngine`.  Each step the engine resolves
the frozen plan variant to run OUTSIDE jit — warmup scheduling
(``ctx.plan.at_step``: identity plan during the warmup window, the
steady plan after) plus every attached controller's proposal — and
dispatches to a per-plan compiled step function; plans are frozen/
hashable, so the cache holds a few entries and jit never sees a varying
policy object.  ``slot=auto`` paths attach a
:class:`repro.core.collectives.SlotController` (renegotiated wire
bounds; overflow -> bit-exact replay, so buffer donation is disabled
while any replay-capable controller is attached) and ``escalate=``
paths an :class:`repro.core.policy.ErrorEscalationController`
(error-driven fallback-codec swaps) — both ride the same cached-step-fn
mechanism.  The normalized spec is persisted in every checkpoint
manifest and validated on restore; per-path wire-byte telemetry is
logged on log steps.

Each iteration is a profiler step span (``train``, with its step number)
holding one span per host phase (``train/data``, ``train/place``,
``train/dispatch``, ``train/sync``, ``train/log``, ``train/ckpt``; names
and meanings in :mod:`repro.core.telemetry`); the straggler watchdog
prints the step's split.  ``TrainerConfig.profile_dir`` captures a trace
of ``profile_steps`` with the compiled step's HLO text beside it.
"""
from __future__ import annotations

import dataclasses
import logging

import jax
import numpy as np

from repro import compat
from repro.ckpt import checkpoint as ckpt
from repro.core import policy, telemetry
from repro.core.registry import to_spec
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.optim import adamw
from repro.runtime.fault_tolerance import (FailureInjector, RetryPolicy,
                                           StepWatchdog)
from repro.train.train_step import build_train_step

log = logging.getLogger("repro.trainer")


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 100
    ckpt_every: int = 50
    log_every: int = 10
    ckpt_dir: str | None = "/tmp/repro_ckpt"   # None: no checkpoints
    keep_last: int = 3
    seed: int = 0
    profile_dir: str | None = None   # trace + step HLO of profile_steps
    profile_steps: tuple[int, int] = (5, 4)   # (first step, count)


class Trainer:
    def __init__(self, model, mesh, ctx, oc: adamw.OptConfig,
                 tc: TrainerConfig, data: SyntheticLM,
                 injector: FailureInjector | None = None):
        self.model, self.mesh, self.ctx = model, mesh, ctx
        self.oc, self.tc, self.data = oc, tc, data
        self.injector = injector
        self.comm_spec = to_spec(ctx.plan)
        self.watchdog = StepWatchdog()
        self.losses: list = []
        self.reporter = telemetry.Reporter(log)
        # the engine owns plan resolution, the compiled-step cache, and
        # the controller replay protocol; default_controllers attaches
        # what the plan asks for (slot=auto / escalate= paths)
        self.policy = policy.PolicyEngine(
            ctx.plan, self._build_step,
            controllers=policy.default_controllers(
                ctx.plan, reporter=self.reporter))
        log.info("comm plan: %s%s", self.comm_spec,
                 f" [{len(self.policy.controllers)} policy controller(s)]"
                 if self.policy.controllers else "")

    # ---- schedule ----------------------------------------------------------
    @property
    def slots(self):
        """The engine's SlotController when ``slot=auto`` is active on
        any path, else None (back-compat accessor — the PolicyEngine
        owns the controller stack now)."""
        from repro.core.collectives import SlotController
        return self.policy.controller(SlotController)

    def _build_step(self, plan):
        """PolicyEngine build callback: compile one frozen plan variant
        (donation stays off while any replay-capable controller is
        attached, so an invalidated step can be replayed bit-exactly)."""
        rctx = dataclasses.replace(self.ctx, plan=plan)
        return build_train_step(self.model, self.mesh, rctx, self.oc,
                                donate=not self.policy.replayable)

    def step_fn_for(self, step: int):
        """The compiled step function for the plan active at ``step``
        (warmup scheduling AND every controller proposal resolved by the
        PolicyEngine, outside jit — resolved plans are frozen/hashable,
        so each caches its own compiled step; escalation variants and
        the 1/32 negotiation grid keep the cache bounded)."""
        return self.policy.fn_for(step)

    # ---- state ------------------------------------------------------------
    def init_state(self):
        """Fresh (params, opt_state, 0), initialised under jit straight
        into their target shardings: no device ever holds more than its
        shard (an eager init would build the whole model on device 0)."""
        from jax.sharding import NamedSharding
        pspecs = self.model.partition_specs()
        shard = lambda s: NamedSharding(self.mesh, s)  # noqa: E731
        out_shardings = (compat.tree_map(shard, pspecs),
                         compat.tree_map(shard,
                                         adamw.opt_state_pspecs(pspecs)))

        def init(key):
            params = self.model.init(key)
            return params, adamw.init_opt_state(params)

        params, opt_state = jax.jit(init, out_shardings=out_shardings)(
            jax.random.PRNGKey(self.tc.seed))
        return params, opt_state, 0

    def try_restore(self, params_tmpl, opt_tmpl):
        if self.tc.ckpt_dir is None:
            return None
        step = ckpt.latest_step(self.tc.ckpt_dir)
        if step is None:
            return None
        pspecs = self.model.partition_specs()
        ospecs = adamw.opt_state_pspecs(pspecs)
        state, step = ckpt.restore(
            self.tc.ckpt_dir, {"params": params_tmpl, "opt": opt_tmpl},
            mesh=self.mesh, pspecs={"params": pspecs, "opt": ospecs},
            expect_comm_spec=self.comm_spec)
        log.info("restored checkpoint at step %d", step)
        return state["params"], state["opt"], step

    # ---- loop -------------------------------------------------------------
    def run(self, resume: bool = True):
        params, opt_state, start = self.init_state()
        if resume:
            restored = self.try_restore(params, opt_state)
            if restored is not None:
                params, opt_state, start = restored

        retry = RetryPolicy()
        step = start
        stepped = False   # has any step completed in this process?
        bspecs = self.model.batch_pspecs()
        phases = telemetry.StepPhases()
        phase = phases.phase
        profile = None if self.tc.profile_dir is None else \
            telemetry.StepProfile(self.tc.profile_dir, *self.tc.profile_steps)
        try:
            while step < self.tc.total_steps:
                try:
                    if self.injector:
                        self.injector.maybe_fail(step)
                    if profile:
                        profile.before(step)
                    with phases.step(step):
                        with phase(telemetry.SPAN_DATA):
                            rows = self.data.batch(step)
                        with phase(telemetry.SPAN_PLACE):
                            batch = self.data.place(rows, self.mesh, bspecs)
                        # the engine resolves the step's plan, dispatches
                        # the cached compiled step, ticks every controller,
                        # and replays an invalidated step (slot-overflow
                        # resync) until it lands clean — donation is off in
                        # that mode, so the inputs stay alive across a replay
                        with phase(telemetry.SPAN_DISPATCH):
                            (params, opt_state, metrics), plan = \
                                self.policy.run(step, lambda fn: fn(
                                    params, opt_state, batch))
                        with phase(telemetry.SPAN_SYNC):
                            loss = float(metrics["loss"])
                        self.losses.append(loss)
                        with phase(telemetry.SPAN_LOG):
                            if step % self.tc.log_every == 0 \
                                    and log.isEnabledFor(logging.INFO):
                                self._log_step(step, loss, metrics, plan,
                                               phases.ms)
                            split = phases.split()
                            self.watchdog.observe(
                                (split["dispatch"] + split["sync"]) * 1e-3,
                                split)
                        step += 1
                        stepped = True
                        if self.tc.ckpt_dir is not None and (
                                step % self.tc.ckpt_every == 0
                                or step == self.tc.total_steps):
                            with phase(telemetry.SPAN_CKPT):
                                ckpt.save(self.tc.ckpt_dir, step,
                                          {"params": params,
                                           "opt": opt_state},
                                          keep_last=self.tc.keep_last,
                                          comm_spec=self.comm_spec)
                    if profile:
                        profile.after(step, lambda: self.step_fn_for(
                            step - 1)[0].lower(params, opt_state, batch)
                            .compile().as_text())
                except Exception as exc:  # noqa: BLE001 — restart boundary
                    # before the first completed step a failure is a build
                    # fault (compile error, device out of memory):
                    # rebuilding cannot fix it, so it surfaces at once
                    if not stepped or not retry.should_retry(exc):
                        raise
                    params, opt_state, start = self.init_state()
                    restored = self.try_restore(params, opt_state)
                    if restored is not None:
                        params, opt_state, step = restored
                    else:
                        step = 0
        finally:
            if profile:
                profile.stop()
        return params, opt_state, self.losses

    def _log_step(self, step, loss, metrics, plan, ms):
        """The log step's line; its ``comm/*`` and controller telemetry
        (static per-path wire accounting of the plan that ran) at DEBUG."""
        tele = telemetry.comm_metrics(
            plan, spec=self.comm_spec,
            warmup_active=self.policy.warmup_active(step))
        tele.update(self.policy.metrics())
        log.info("step %d loss %.4f gnorm %.3f lr %.2e (%.2fs) "
                 "tp_wire %.3fB/elem", step, loss,
                 float(metrics["grad_norm"]), float(metrics["lr"]),
                 (ms["dispatch"] + ms["sync"]) * 1e-3,
                 tele["comm/tp_fwd_bytes_per_elem"])
        log.debug("step %d telemetry %s", step, tele)
