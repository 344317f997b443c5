"""Training traffic: packed documents from a seed, and the data source the
trainer's own loop pulls them from.

``pack_rows`` builds every row a run can need during set-up, vectorised
over rows.  Each row of ``seq + 1`` tokens is packed with documents whose
lengths are drawn from a log-normal (heavy tail), each document followed
by the EOS token and the last one cut at the row's end.  Token ids follow
a seeded Markov chain of the same kind as the program's ``SyntheticLM``
(64 hidden states, each preferring 8 successors, 10% random jumps), so the
loss falls as the model learns it.  Every seed gives rows of the same
shape, so a seed changes the tokens and never the work.

``Feed`` is handed to ``Trainer`` as ``trainer.data``.  It times the loop
at its ``batch(step)`` calls, opens the measured window at the first call
after the warm-up steps, and closes it by raising ``WindowClosed``, which
is not an ``Exception`` and so passes the trainer's restart handler.  A
step index that repeats or goes back means the trainer restarted after a
failure; each such call counts as a failed step.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

MARKOV_STATES = 64
SUCCESSORS = 8
JUMP_P = 0.1


class WindowClosed(BaseException):
    """Raised from ``Feed.batch`` to end ``Trainer.run`` at the window's end."""


def seed_words(seed: int) -> tuple[int, int]:
    """Two 31-bit words from any whole-number seed (JAX keys take 32 bits)."""
    s = np.random.SeedSequence(int(seed)).generate_state(2, dtype=np.uint32)
    return int(s[0]) & 0x7FFFFFFF, int(s[1]) & 0x7FFFFFFF


def pack_rows(traffic: dict, *, vocab: int, eos: int, seq: int, rows: int,
              seed: int) -> np.ndarray:
    """``rows`` packed rows of ``seq + 1`` token ids (int32) from ``seed``."""
    rng = np.random.default_rng([int(seed), 0x7AC0])
    width = seq + 1
    lo, hi = int(traffic["doc_min_tokens"]), int(traffic["doc_max_tokens"])
    # enough documents that even all-shortest ones fill a row
    per_row = width // (lo + 1) + 1
    lengths = np.exp(rng.normal(traffic["doc_log_mean"],
                                traffic["doc_log_sigma"], (rows, per_row)))
    lengths = np.clip(np.rint(lengths), lo, hi).astype(np.int64)
    ends = np.cumsum(lengths + 1, axis=1) - 1          # EOS positions
    is_eos = np.zeros((rows, width + 1), bool)
    r_idx = np.broadcast_to(np.arange(rows)[:, None], ends.shape)
    keep = ends < width
    is_eos[r_idx[keep], ends[keep]] = True
    is_eos = is_eos[:, :width]
    starts = np.zeros_like(is_eos)
    starts[:, 0] = True
    starts[:, 1:] = is_eos[:, :-1]

    # the chain's tables, and every draw, made up front in bulk
    state_of = rng.integers(0, MARKOV_STATES, vocab)
    prefs = rng.integers(0, vocab, (MARKOV_STATES, SUCCESSORS))
    fresh = rng.integers(0, vocab, (rows, width))
    choice = rng.integers(0, SUCCESSORS, (rows, width))
    jump = (rng.random((rows, width)) < JUMP_P) | starts
    fresh = np.where(fresh == eos, (fresh + 1) % vocab, fresh)

    out = np.empty((rows, width), np.int32)
    cur = fresh[:, 0]
    for t in range(width):
        if t:
            nxt = prefs[state_of[cur], choice[:, t]]
            cur = np.where(jump[:, t], fresh[:, t], nxt)
        out[:, t] = np.where(is_eos[:, t], eos, cur)
    return out


@dataclasses.dataclass
class WindowLog:
    """What the data source saw: step stamps and the window's bounds."""
    window_start: float | None = None   # perf_counter at the first window call
    window_end: float | None = None     # perf_counter at the closing call
    stamps: list = dataclasses.field(default_factory=list)
    untimed: list = dataclasses.field(default_factory=list)  # set-up calls
    failed: int = 0
    host_batch_s: float = 0.0           # host time spent inside batch()
    batch_calls: int = 0


class Feed:
    """The trainer's data source: ``batch(step)`` and ``place(...)``.

    ``warmup`` steps run before the window; the window then lasts
    ``seconds``.  With ``trace_steps`` set, a profiler trace of that many
    further steps follows the window (``tracer`` starts and stops it), and
    host spans mark the time spent here (``data``) and in the trainer's own
    loop (``trainer``)."""

    def __init__(self, rows: np.ndarray, batch: int, *, warmup: int,
                 seconds: float, tracer=None, trace_steps: int = 0,
                 on_window_start=None):
        self.rows = rows
        self.b = batch
        self.seq = rows.shape[1] - 1
        self.warmup = warmup
        self.seconds = seconds
        self.tracer = tracer
        self.trace_steps = trace_steps
        self.on_window_start = on_window_start
        self.log = WindowLog()
        self._mask = np.ones((batch, self.seq), np.float32)
        self._last = -1
        self._trace_from = None
        self._span = None

    def rows_for(self, step: int) -> np.ndarray:
        n = self.rows.shape[0]
        idx = (step * self.b + np.arange(self.b)) % n
        return self.rows[idx]

    def host_batch(self, step: int) -> dict:
        r = self.rows_for(step)
        return {"tokens": r[:, :-1], "labels": r[:, 1:], "mask": self._mask}

    def batch(self, step: int) -> dict:
        now = time.perf_counter()
        self._enter_span("data")
        lg = self.log
        if step <= self._last:
            lg.failed += 1
        self._last = step
        if step >= self.warmup and lg.window_start is None:
            lg.window_start = now
            if self.on_window_start is not None:
                self.on_window_start()
        if lg.window_start is None:
            lg.untimed.append(now)
        else:
            if lg.window_end is None:
                lg.stamps.append(now)
            if self._trace_from is None \
                    and now - lg.window_start >= self.seconds:
                lg.window_end = now
                if not self.trace_steps:
                    self._exit_span()
                    raise WindowClosed
                self._trace_from = step
                self.tracer.start()
                self._enter_span("data")
            elif self._trace_from is not None \
                    and step - self._trace_from >= self.trace_steps:
                self._exit_span()
                self.tracer.stop()
                raise WindowClosed
        out = self.host_batch(step)
        lg.batch_calls += 1
        lg.host_batch_s += time.perf_counter() - now
        self._enter_span("trainer")
        return out

    def place(self, batch: dict, mesh, bspecs) -> dict:
        import jax
        from jax.sharding import NamedSharding
        return {k: jax.device_put(v, NamedSharding(mesh, bspecs[k]))
                for k, v in batch.items()}

    # host spans on the profiler's clock (recorded only while tracing)
    def _enter_span(self, name: str):
        if self._trace_from is None:
            return
        self._exit_span()
        import jax
        self._span = jax.profiler.TraceAnnotation(f"bench/{name}")
        self._span.__enter__()

    def _exit_span(self):
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
