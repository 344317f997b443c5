"""One run of one cell: set-up, the measured window, the comparison that
decides ``correct``, and the result line.

The window is driven by the program's own training loop: the trainer is
built through ``repro.launch.train.build_parser``/``build_trainer`` with
checkpoints off, ``feed.Feed`` is its data source, and ``Trainer.run``
compiles, warms up and runs the window in one call.  Three hooks are set
on the trainer object, none of them in the timed path:

* ``init_state`` returns the benchmark's weights (``weights.py``) in the
  program's tree and shardings, made in one jitted call from the seed;
* ``oc`` is the benchmark's optimizer setting (``OPT``);
* ``policy.run`` is wrapped for the inputs of steps 1 and 3 only, to read
  the per-leaf norms of the first gradient (from Adam's first moment) and
  of the parameters' change after three updates; the wrapper removes
  itself after step 3.

After the window the program's state is dropped and the configuration's
float32 reference (``cell.reference``; ``refmodel.py`` unless the
configuration names another) follows the same three steps from the same
weights and rows; ``compare.py`` turns both into the numbers compared.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import glob
import json
import math
import os
import shutil
import sys
import time
import typing

import numpy as np

import cells
import compare
import feed as feed_mod
import refmodel
import weights

CAPTURE_GRAD, CAPTURE_DELTA = 1, 3   # step inputs the tap reads
UNTIMED_STEPS = 5    # steps before the window: compile and warm-up
TRACE_STEPS = 4      # steps traced after the window with --trace 1
POOL_ROWS = 256      # distinct packed rows a run draws; the feed wraps
OPT = {"lr_max": 3e-4, "lr_min": 3e-5, "warmup_steps": 10,
       "total_steps": 10000, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
       "weight_decay": 0.1, "clip_norm": 1.0}


def process_age() -> float:
    """Seconds since this process started (``/proc``; else since import)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        return time.clock_gettime(time.CLOCK_BOOTTIME) \
            - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _IMPORTED


_IMPORTED = time.perf_counter()


def say(*parts):
    print(*parts, file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


# ---------------------------------------------------------------------------
# devices
# ---------------------------------------------------------------------------

def devices_for(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devs[0].platform} devices "
                         f"({devs[0].device_kind}); the benchmark runs on a "
                         f"chip only")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chips; JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


def enable_cache():
    """JAX's persistent compilation cache at one fixed path in the
    checkout, holding every program the run compiles, with no eviction
    (whose bookkeeping fails on entries written without it)."""
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(cells.ROOT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

def build_trainer(cell: cells.Cell):
    sys.path.insert(0, str(cells.ROOT / "src"))
    from repro.configs.base import ArchConfig
    from repro.launch.train import build_parser, build_trainer as build
    from repro.optim.adamw import OptConfig

    r = cell.run
    args = build_parser().parse_args([
        "--arch", r["program_arch"], "--mesh", r["mesh"],
        "--comm-spec", r["comm_spec"], "--seq", str(cell.seq),
        "--batch", str(cell.batch), "--steps", str(OPT["total_steps"]),
        "--ckpt", "", "--no-resume"])
    trainer = build(args, program_config(ArchConfig, cell.arch))
    trainer.oc = OptConfig(**OPT)
    trainer.tc.total_steps = 1 << 40     # the window, not a count, ends it
    return trainer


def program_config(cls, block: dict):
    """The program's configuration object ``cls`` of ``block``: each nested
    object becomes the dataclass that ``cls`` annotates its field with
    (``ArchConfig.moe`` a ``MoeConfig``); a key ``cls`` lacks ends the run."""
    hints = typing.get_type_hints(cls)
    fields = {f.name for f in dataclasses.fields(cls)}
    unknown = sorted(set(block) - fields)
    if unknown:
        raise SystemExit(f"the program's {cls.__name__} has no field "
                         f"{', '.join(unknown)}")
    out = {}
    for k, v in block.items():
        if isinstance(v, dict):
            kinds = [t for t in typing.get_args(hints[k]) or (hints[k],)
                     if dataclasses.is_dataclass(t)]
            if not kinds:
                raise SystemExit(f"the program's {cls.__name__}.{k} takes "
                                 f"no nested block")
            v = program_config(kinds[0], v)
        out[k] = v
    return cls(**out)


def program_layout(trainer) -> dict:
    """What the per-layer readers take from the program: its chips per
    tensor-parallel group and its rematerialisation."""
    plan = trainer.model.plan
    return {"tp": plan.tp,
            "remat": plan.remat_policy if plan.remat else "none"}


class ProgramHooks:
    """The benchmark's weights in, the per-leaf readings out."""

    def __init__(self, trainer, cell: cells.Cell):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding
        from repro import compat
        from repro.optim import adamw

        self.trainer = trainer
        model, mesh = trainer.model, trainer.mesh
        abstract = model.abstract_params()
        flat, self.treedef = jax.tree_util.tree_flatten_with_path(abstract)
        self.names = [weights.program_name(p) for p, _ in flat]
        self.shapes = {n: tuple(a.shape) for n, (_, a) in
                       zip(self.names, flat)}
        pos = self.shapes.get("pos_embed", (0,))[0]
        ref = cell.reference
        want = ref.shapes(cell.arch, pos)
        if want != self.shapes:
            raise SystemExit(f"the program's parameters do not map onto the "
                             f"reference's: {sorted(set(want.items()) ^ set(self.shapes.items()))[:6]}")
        stated = cell.config["precision"]["compute"]
        dtypes = {str(a.dtype) for _, a in flat}
        if dtypes != {stated}:
            raise SystemExit(f"the program computes in {sorted(dtypes)}; the "
                             f"configuration states {stated}")
        shard = lambda s: NamedSharding(mesh, s)  # noqa: E731
        pspecs = model.partition_specs()
        out = (compat.tree_map(shard, pspecs),
               compat.tree_map(shard, adamw.opt_state_pspecs(pspecs)))
        names, shapes, treedef = self.names, self.shapes, self.treedef

        def make(key):
            leaves = [weights.draw(key, names, n, shapes[n]) for n in names]
            params = jax.tree_util.tree_unflatten(treedef, leaves)
            return params, adamw.init_opt_state(params)

        def named(tree):
            return dict(zip(names, jax.tree_util.tree_leaves(tree)))

        b1 = trainer.oc.b1

        def grad_readings(mu, idx):
            g = {k: v / (1.0 - b1) for k, v in named(mu).items()}
            return ref.leaf_norms(g), compare.take_sample(g, idx)

        def delta_norms(master, key):
            return ref.leaf_norms(
                {k: v - weights.draw(key, names, k, shapes[k]).astype(
                    jnp.float32) for k, v in named(master).items()})

        self._make = jax.jit(make, out_shardings=out)
        self._grad = jax.jit(grad_readings)
        self._delta = jax.jit(delta_norms)
        self.key = None
        self.readings = {}

    def arm(self, seed: int):
        """Set the weights of the next ``Trainer.run`` and arm the tap."""
        self.key = weights.base_key(seed)
        self.idx = compare.sample_index(self.shapes, seed)
        self.readings = {}
        tr = self.trainer
        tr.init_state = lambda: (*self._make(self.key), 0)
        orig = type(tr.policy).run.__get__(tr.policy)

        def run(step, invoke):
            if step not in (CAPTURE_GRAD, CAPTURE_DELTA) \
                    or step in self.readings:
                return orig(step, invoke)

            def tapped(fn):
                def call(params, opt, batch):
                    if step not in self.readings:
                        self._capture(step, opt)
                    return fn(params, opt, batch)
                return invoke(call)

            out = orig(step, tapped)
            if CAPTURE_DELTA in self.readings:
                del tr.policy.run      # back to the class's own method
            return out

        tr.policy.run = run

    def _capture(self, step, opt):
        import jax
        if step == CAPTURE_GRAD:
            self.readings[step] = jax.device_get(
                self._grad(opt["mu"], self.idx))
        else:
            self.readings[step] = jax.device_get(
                self._delta(opt["master"], self.key))

    def program_readings(self) -> dict:
        losses = list(self.trainer.losses[:3])
        if len(losses) < 3 or CAPTURE_DELTA not in self.readings:
            raise RuntimeError("the program did not complete its first "
                               "four steps")
        norms, sample = self.readings[CAPTURE_GRAD]
        return {"loss": losses, "grad": norms, "grad_sample": sample,
                "delta": self.readings[CAPTURE_DELTA]}


def step_hlo(trainer, cell: cells.Cell) -> str:
    """The compiled HLO text of the step the window ran (lowered again on
    abstract arguments; the compile is found in the cache)."""
    import jax
    from jax.sharding import NamedSharding
    from repro import compat
    from repro.optim import adamw

    model, mesh = trainer.model, trainer.mesh

    def sds(a, spec):
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=NamedSharding(mesh, spec))

    pspecs = model.partition_specs()
    abstract = model.abstract_params()
    params = compat.tree_map(sds, abstract, pspecs)
    opt = compat.tree_map(sds, adamw.abstract_opt_state(abstract),
                          adamw.opt_state_pspecs(pspecs))
    bspecs = model.batch_pspecs()
    data = {k: sds(v, bspecs[k]) for k, v in
            model.batch_shape(cell.seq, cell.batch).items()}
    fn, _ = trainer.step_fn_for(UNTIMED_STEPS)
    return fn.lower(params, opt, data).compile().as_text()


class Tracer:
    def __init__(self, path):
        self.path = path

    def start(self):
        import jax
        shutil.rmtree(self.path, ignore_errors=True)
        jax.profiler.start_trace(str(self.path))

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def xplane(self) -> str:
        found = sorted(glob.glob(f"{self.path}/**/*.xplane.pb",
                                 recursive=True))
        if not found:
            raise RuntimeError(f"no trace under {self.path}")
        return found[-1]


def make_rows(cell: cells.Cell, seed: int):
    """The run's pool of packed rows, and the generator's host time per
    step of rows."""
    t0 = time.perf_counter()
    rows = feed_mod.pack_rows(cell.traffic, vocab=cell.arch["vocab_size"],
                              eos=cell.config["eos_token_id"], seq=cell.seq,
                              rows=POOL_ROWS, seed=seed)
    gen = time.perf_counter() - t0
    return rows, gen * cell.batch / POOL_ROWS


def run_program(trainer, hooks: ProgramHooks, cell: cells.Cell, seed: int,
                rows, seconds: float, tracer=None):
    """``Trainer.run`` from the seed's weights to the window's close."""
    marks = {}
    fd = feed_mod.Feed(rows, cell.batch, warmup=UNTIMED_STEPS,
                       seconds=seconds, tracer=tracer,
                       trace_steps=TRACE_STEPS if tracer else 0,
                       on_window_start=lambda: marks.setdefault(
                           "setup_s", process_age()))
    trainer.data = fd
    trainer.losses = []
    hooks.arm(seed)
    try:
        trainer.run(resume=False)
    except feed_mod.WindowClosed:
        pass
    gc.collect()
    return fd, marks.get("setup_s")


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _ref_fns(ref, arch_json: str, shapes_json: str, var: refmodel.Variant,
             wire: refmodel.Wire, devices: tuple):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    arch = json.loads(arch_json)
    shapes = {k: tuple(v) for k, v in json.loads(shapes_json).items()}
    mesh = Mesh(np.array(devices), ("r",))
    n = len(devices)

    def spec(shape):
        dims = [None] * len(shape)
        lo = 1 if len(shape) > 2 else 0
        cand = [i for i in range(lo, len(shape)) if shape[i] % n == 0]
        if n > 1 and cand:
            dims[max(cand, key=lambda i: shape[i])] = "r"
        return NamedSharding(mesh, P(*dims))

    shard = {k: spec(s) for k, s in shapes.items()}
    init = jax.jit(lambda key: weights.reference_weights(key, shapes),
                   out_shardings=shard)
    zeros = jax.jit(lambda: {k: jnp.zeros(s, jnp.float32)
                             for k, s in shapes.items()},
                    out_shardings=shard)
    def train(p, m, v, t, batch, idx):
        p, m, v, lval, g = ref.train_step(p, m, v, t, batch, arch, OPT, var,
                                          wire)
        return p, m, v, lval, ref.leaf_norms(g), compare.take_sample(g, idx)

    step = jax.jit(train, donate_argnums=(0, 1, 2),
                   out_shardings=(shard, shard, shard, None, None, None))
    delta = jax.jit(lambda a, b: ref.leaf_norms(
        {k: a[k] - b[k] for k in a}))
    return init, zeros, step, delta, NamedSharding(mesh, P())


def reference_readings(cell: cells.Cell, shapes: dict, seed: int, fd,
                       devices, var: refmodel.Variant = refmodel.SOUND,
                       half: bool = False) -> dict:
    """The reference's three steps from the seed's weights on the rows of
    steps 0..2; ``half`` leaves out the second half of every row."""
    import jax
    import jax.numpy as jnp
    ref = cell.reference
    init, zeros, step, delta, repl = _ref_fns(
        ref, json.dumps(cell.arch, sort_keys=True),
        json.dumps(shapes, sort_keys=True), var,
        ref.wire_of(cell.config, cell.tp), tuple(devices))
    key = weights.base_key(seed)
    idx = jax.device_put(compare.sample_index(shapes, seed), repl)
    losses, grad, sample = [], None, None
    with jax.default_matmul_precision("highest"):
        p, m, v = init(key), zeros(), zeros()
        for t in range(3):
            b = dict(fd.host_batch(t))
            if half:
                b["mask"] = b["mask"].copy()
                b["mask"][:, b["mask"].shape[1] // 2:] = 0.0
            b = jax.device_put(b, repl)
            p, m, v, lval, g, smp = step(p, m, v, jnp.int32(t), b, idx)
            losses.append(float(lval))
            if t == 0:
                grad, sample = jax.device_get((g, smp))
        del m, v
        d = jax.device_get(delta(p, init(key)))
    del p
    return {"loss": losses, "grad": grad, "grad_sample": sample, "delta": d}


def checks(prog: dict, ref: dict, limits: dict) -> dict:
    out = {}
    for name, (value, where) in compare.gaps(prog, ref).items():
        out[name] = {"value": value, "limit": limits[name], "at": where}
    return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LayerRun:
    """What a per-layer metric's reader is given."""
    trace: object
    tokens_per_s: float | None
    flops_per_token: float   # model FLOPs per token, from the reference
    arch: dict
    wire: dict | None    # the configuration's compressed wire, if any
    tp: int              # the program's chips per tensor-parallel group
    remat: str           # the program's rematerialisation policy
    seq: int
    batch: int
    chips: int
    peaks: dict


def read_metric(name: str, lr: LayerRun):
    import importlib
    mod = importlib.import_module(f"metrics.{name}")
    return mod.read(lr)


def window_numbers(fd) -> dict:
    st = np.asarray(fd.log.stamps)
    steps = len(st) - 1
    if steps < 1:
        raise RuntimeError("the window closed before a step completed")
    dt = np.diff(st)
    return {"steps": steps, "window_s": float(st[-1] - st[0]),
            "step_s": dt}


def main(argv=None, *, require_chip: bool = True,
         benchmark=None) -> dict:
    args = parse(argv)
    cell = cells.load(args.workload, benchmark)
    marks = [("process start", time.perf_counter() - process_age())]
    import jax
    devices = devices_for(cell.chips, require_chip)
    marks.append(("imports and devices", time.perf_counter()))
    kind = devices[0].device_kind
    peaks = cells.peaks(kind) if require_chip else None
    enable_cache()
    compiles, cache = [], {"hits": 0, "misses": 0}

    def on_duration(ev, dur, **kw):
        if ev == "/jax/core/compile/backend_compile_duration":
            compiles.append((time.perf_counter(), dur))

    def on_event(ev, **kw):
        if ev == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif ev == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    trainer = build_trainer(cell)
    hooks = ProgramHooks(trainer, cell)
    marks.append(("trainer build", time.perf_counter()))
    rows, gen_per_step = make_rows(cell, args.seed)
    marks.append(("traffic", time.perf_counter()))
    say(f"traffic: {rows.shape[0]} packed rows of {rows.shape[1] - 1} "
        f"tokens; generator host time {gen_per_step * 1e3:.4f} ms per step")
    tracer = Tracer(cells.ROOT / ".bench_trace" / cell.name) \
        if args.trace else None
    fd, setup_s = run_program(trainer, hooks, cell, args.seed, rows,
                              args.seconds, tracer)
    prog = hooks.program_readings()
    win = window_numbers(fd)
    un = fd.log.untimed
    marks += [("weights (init_state)", un[0]),
              ("step 0 (compile or cache load)", un[1]),
              (f"steps 1-{len(un) - 1}", fd.log.window_start)]
    say("setup split: " + "; ".join(
        f"{name} {t - marks[i][1]:.3f} s"
        for i, (name, t) in enumerate(marks[1:])))
    lo, hi = fd.log.window_start, fd.log.window_end
    in_window = sum(1 for t, _ in compiles if lo <= t <= hi)
    say(f"programs compiled or loaded before the window: "
        f"{sum(1 for t, _ in compiles if t < lo)} taking "
        f"{sum(d for t, d in compiles if t < lo):.3f} s; persistent cache "
        f"hits {cache['hits']}, misses {cache['misses']}")
    slow = np.argsort(win["step_s"])[::-1][:3]
    say("slowest window steps (index, s): "
        + ", ".join(f"({i}, {win['step_s'][i]:.3f})" for i in slow)
        + f"; median {np.median(win['step_s']):.4f} s")
    say(f"window: {win['steps']} steps in {win['window_s']:.3f} s; "
        f"data source host time {fd.log.host_batch_s / max(fd.log.batch_calls, 1) * 1e3:.4f} "
        f"ms per batch() call; programs compiled or loaded inside the window: {in_window}")
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
               for d in devices) if require_chip else 0
    n_failed = fd.log.failed
    kernels = None
    layout = program_layout(trainer)
    if args.trace:
        import xtrace
        kernels = xtrace.kernel_symbols(step_hlo(trainer, cell))

    # the program's state went with Trainer.run's frame; the reference
    # runs on the freed chips
    del trainer.data
    hooks.trainer = None
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_readings(cell, hooks.shapes, args.seed, fd, devices)
    say(f"reference: {time.perf_counter() - t_ref:.3f} s")
    chk = checks(prog, ref, cell.run["limits"])
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in chk.values())

    tokens = cell.seq * cell.batch
    tps = win["steps"] * tokens / win["window_s"]
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": win["steps"] + n_failed,
              "failed": n_failed}
    if not args.trace:
        result["metrics"] = {
            "tokens_per_s": {"value": tps, "unit": "tokens/s"},
            "step_p90_ms": {"value": float(np.percentile(
                win["step_s"], 90) * 1e3), "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    else:
        import xtrace
        t0 = time.perf_counter()
        tr = xtrace.load(tracer.xplane(), kernels)
        lr = LayerRun(trace=tr, tokens_per_s=tps,
                      flops_per_token=cell.reference.flops_per_token(
                          cell.arch, cell.seq), arch=cell.arch,
                      wire=cell.config["precision"].get("tp_wire"),
                      seq=cell.seq, batch=cell.batch, chips=len(devices),
                      peaks=peaks, **layout)
        metrics = {}
        for m in cell.per_layer:
            v = read_metric(m["name"], lr)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        busy = [xtrace.busy_ns(tr, d) * 1e-9 for d in tr.ops]
        device["busy_s"] = sum(busy) / max(len(busy), 1)
        device["window_s"] = tr.window_s
        result["metrics"] = metrics
        result["breakdown"] = xtrace.breakdown(tr)
        say(f"trace: {tr.steps} steps, {sum(len(v) for v in tr.ops.values())} "
            f"device ops, reduced in {time.perf_counter() - t0:.1f} s")
        if "codec_kernel_roofline" in metrics:
            import importlib
            rf = importlib.import_module("metrics.codec_kernel_roofline")
            ck = importlib.import_module("metrics.codec_kernel_ms")
            per = {k: (ns * 1e-6 / len(tr.ops) / tr.steps,
                       n / len(tr.ops) / tr.steps)
                   for k, (ns, n) in ck.per_kernel_ns(tr).items()}
            say(f"codec kernels per step and chip (ms, calls): {per}; "
                f"counted hops (gathers, scatters): {rf.hops(lr)}; "
                f"roofline bound by {rf.bound(lr)}")
        shutil.rmtree(tracer.path, ignore_errors=True)
    result["device"] = device
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in chk.items()}
    print(json.dumps(result), flush=True)
    for k, c in chk.items():
        say(f"check {k} {c['value']!r} limit {c['limit']!r} (worst at "
            f"{c['at']})")
    return result
