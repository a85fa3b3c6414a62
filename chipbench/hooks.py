"""What the benchmark lays around the program's own training loop.

``launch.train.train()`` runs its loop to the end by itself; it has no step
hook and no deadline.  ``TrainHooks.installed(T)`` swaps, for the length of a
``with`` block, a few names that ``train()`` looks up in its module ``T``:

- ``model_config``: the program's model config, with the fields the
  architecture module sets as the configuration states (its
  ``configured``);
- ``make_pipeline`` and ``lm.init_model``: the benchmark's seeded tokens and
  the architecture's seeded weights, so that the reference can make the same
  ones again;
- ``Prefetcher``: the program's prefetcher, whose ``get`` also opens the
  measured window at the first step after set-up and closes it at the
  deadline (``WindowClosed``); in a mix that saves, it picks instead the
  first save after the deadline with ``WHOLE_SAVES`` whole saves behind it,
  in which the program's own fault injection
  (``RunConfig.die_mid_checkpoint_at``) crashes the run;
- ``jit_train_step``: the program's jitted step, wrapped to keep each step's
  loss and, in set-up, the first gradient's and the first steps' change
  norms for the comparison with the reference;
- ``_checkpoint``, ``partition_leaves``, ``pack_tree``,
  ``CornusCheckpointer.vote``/``resolve``, ``latest_committed`` and
  ``restore_params``: timed, and named as host spans in a traced run.

The fingerprints of the saved and the restored states, which the commit
checks compare, are the benchmark's own work: their time is taken out of
the window, the save's stall and the resume.  Everything is put back when
the block ends.
"""
from __future__ import annotations

import contextlib
import gc
import time
from typing import Dict, List, Optional

import jax

from . import weights as W

# Host spans that a traced run writes into the profiler's trace, the
# wrappers' and the program's own; the trace reduction names device idle
# time by the innermost of these.
SPANS = ("window", "data", "train_step", "checkpoint", "partition", "pack",
         "vote", "resolve", "restore", "step", "h2d", "loss_sync", "d2h",
         "upload", "log_once")

_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/compilation_cache/cache_retrieval_time_sec")


# Whole saves a window of a mix that saves holds before the one it crashes in.
WHOLE_SAVES = 2


class WindowClosed(Exception):
    """Raised from the prefetcher to end ``train()`` at the deadline."""


def _full(params, opt_state):
    return {"params": params, "opt": {"m": opt_state["m"],
                                      "v": opt_state["v"]}}


class TrainHooks:
    """``arch`` is the configuration's architecture module, ``dm`` its
    dimensions."""

    def __init__(self, *, arch, dm: dict, seed: int, tokens: W.SeededTokens,
                 setup_steps: int, ref_steps: int, seconds: float,
                 ckpt_every: int, trace_dir: Optional[str]):
        if ref_steps >= setup_steps:
            raise ValueError("the reference's steps must end in set-up")
        self.arch, self.dm, self.seed, self.tokens = arch, dm, seed, tokens
        self.setup_steps, self.ref_steps = setup_steps, ref_steps
        self.seconds, self.ckpt_every = seconds, ckpt_every
        self.trace_dir = trace_dir
        self.phase = "run"
        self.step = -1
        self.t_window: Optional[float] = None
        self.t_end: Optional[float] = None
        self.paused_s = 0.0  # the benchmark's own work inside the window
        self.window_steps = 0
        self.crash_epoch: Optional[int] = None
        self.losses: Dict[int, jax.Array] = {}
        self.grad_norms = None
        self.change_norms = None
        self.saves: List[dict] = []
        self.restores: List[dict] = []
        self.latest: List[dict] = []
        self.compiles_in_window = 0
        self.step_marks: List[float] = []
        # Per window step: seconds in the prefetcher's get and in the
        # step's dispatch, for the log line's account of the slowest step.
        self.step_parts: List[List[float]] = []
        self.gc_in_window_s = 0.0
        self._gc_t0 = 0.0
        self.resume_marks: List[Dict[str, float]] = []
        self._window_span = None

    # -- spans and the window ------------------------------------------------
    def span(self, name: str):
        if self.trace_dir is None:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(name)

    def _on_gc(self, phase: str, _info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self.window_open:
            self.gc_in_window_s += time.perf_counter() - self._gc_t0

    def _on_compile(self, event: str, _secs: float, **_kw) -> None:
        if event in _COMPILE_EVENTS and self.window_open:
            self.compiles_in_window += 1

    @property
    def window_open(self) -> bool:
        return self.t_window is not None and self.t_end is None

    def _open_window(self) -> None:
        if self.trace_dir is not None:
            # Host spans come from TraceAnnotation; the Python tracer, which
            # records every Python call, would slow the host path it traces.
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._window_span = jax.profiler.TraceAnnotation("window")
            self._window_span.__enter__()
        self.t_window = time.perf_counter()

    def _close_window(self, steps: int) -> None:
        self.t_end = time.perf_counter()
        self.window_steps = steps
        if self.trace_dir is not None:
            self._window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()

    @property
    def window_s(self) -> float:
        return self.t_end - self.t_window - self.paused_s

    def _own_work(self, fn, *args):
        """Run the benchmark's ``fn`` to completion; (its result, seconds)."""
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        secs = time.perf_counter() - t0
        if self.window_open:
            self.paused_s += secs
        return out, secs

    def window_saves(self) -> List[dict]:
        return [s for s in self.saves if s["in_window"]]

    def _before_get(self, s: int) -> None:
        if self.phase != "run":
            return
        if s == self.setup_steps:
            self._open_window()
        if self.window_open:
            self.step_marks.append(time.perf_counter())
            self.step_parts.append([0.0, 0.0])
        if s == self.setup_steps:
            return
        if not self.window_open:
            return
        due = (time.perf_counter() - self.t_window - self.paused_s
               >= self.seconds)
        if not self.ckpt_every:
            if due:
                self._close_window(s - self.setup_steps)
                raise WindowClosed
            return
        # Step s is the last before a save: crash in that save.
        whole = len(self.window_saves()) >= WHOLE_SAVES
        if due and whole and (s + 1) % self.ckpt_every == 0:
            self.crash_epoch = s + 1

    # -- wrappers ------------------------------------------------------------
    def _prefetcher(self, base):
        hooks = self

        class WindowPrefetcher(base):
            def __init__(self, source, start_step, *a, **kw):
                super().__init__(source, start_step, *a, **kw)
                self._next = start_step

            def get(self):
                hooks._before_get(self._next)
                t0 = time.perf_counter()
                with hooks.span("data"):
                    item = super().get()
                if hooks.window_open:
                    hooks.step_parts[-1][0] = time.perf_counter() - t0
                hooks.step = item[0]
                self._next = item[0] + 1
                return item

        return WindowPrefetcher

    def _step(self, inner):
        hooks = self

        def train_step(params, opt_state, batch, step):
            t0 = time.perf_counter()
            with hooks.span("train_step"):
                p, o, loss = inner(params, opt_state, batch, step)
            if hooks.window_open:
                hooks.step_parts[-1][1] = time.perf_counter() - t0
            s = hooks.step
            if hooks.phase != "run":
                hooks.resume_marks[-1].setdefault("step_dispatched",
                                                  time.perf_counter())
                return p, o, loss
            hooks.losses[s] = loss
            if s == 0:
                hooks.grad_norms = W.slice_norms(*hooks.arch.slices(o["m"]))
            if s == hooks.ref_steps - 1:
                hooks.change_norms = hooks.arch.change_norms(hooks.dm, p,
                                                             hooks.seed)
            if s == hooks.setup_steps - 1 and hooks.ckpt_every:
                # Compile the save's fingerprint in set-up, not in the window.
                jax.block_until_ready(W.fingerprint(_full(p, o)))
            return p, o, loss

        return train_step

    def _checkpoint(self, inner):
        hooks = self

        def checkpoint(run, cfg, params, opt_state, epoch, *rest):
            if hooks.phase == "run" and epoch == hooks.crash_epoch:
                hooks._close_window(epoch - hooks.setup_steps)
                run.die_mid_checkpoint_at = epoch
                return inner(run, cfg, params, opt_state, epoch, *rest)
            fp, _ = hooks._own_work(W.fingerprint, _full(params, opt_state))
            t0 = time.perf_counter()
            with hooks.span("checkpoint"):
                out = inner(run, cfg, params, opt_state, epoch, *rest)
            hooks.saves.append(dict(
                epoch=epoch, stall_s=time.perf_counter() - t0, outcome=out,
                fingerprint=fp, in_window=hooks.window_open))
            return out

        return checkpoint

    def _spanned(self, name: str, inner):
        hooks = self

        def call(*a, **kw):
            with hooks.span(name):
                return inner(*a, **kw)

        return call

    def _latest(self, inner):
        hooks = self

        def latest_committed(*a, **kw):
            t0 = time.perf_counter()
            with hooks.span("resolve"):
                epoch = inner(*a, **kw)
            hooks.latest.append(dict(epoch=epoch,
                                     secs=time.perf_counter() - t0))
            return epoch

        return latest_committed

    def _restore(self, inner):
        hooks = self

        def restore_params(store, hosts, epoch, template):
            t0 = time.perf_counter()
            with hooks.span("restore"):
                out = inner(store, hosts, epoch, template)
            secs = time.perf_counter() - t0
            fp, fp_secs = hooks._own_work(W.fingerprint, out)
            hooks.restores.append(dict(epoch=epoch, secs=secs,
                                       fingerprint=fp, own_s=fp_secs))
            return out

        return restore_params

    @contextlib.contextmanager
    def installed(self, T):
        """Lay the hooks around the training module ``T`` for a block."""
        from repro.ckpt.commit import CornusCheckpointer

        arch, dm, seed = self.arch, self.dm, self.seed
        jit_step = T.jit_train_step
        swaps = [
            (T, "model_config", arch.configured(T.model_config, dm)),
            (T, "make_pipeline", lambda _dcfg: self.tokens),
            (T.lm, "init_model",
             lambda _cfg, _rng, dtype=None: arch.program_weights(dm, seed)),
            (T, "Prefetcher", self._prefetcher(T.Prefetcher)),
            (T, "jit_train_step",
             lambda cfg, run: self._step(jit_step(cfg, run))),
            (T, "_checkpoint", self._checkpoint(T._checkpoint)),
            (T, "partition_leaves",
             self._spanned("partition", T.partition_leaves)),
            (T, "pack_tree", self._spanned("pack", T.pack_tree)),
            (T, "latest_committed", self._latest(T.latest_committed)),
            (T, "restore_params", self._restore(T.restore_params)),
            (CornusCheckpointer, "vote",
             self._spanned("vote", CornusCheckpointer.vote)),
            (CornusCheckpointer, "resolve",
             self._spanned("resolve", CornusCheckpointer.resolve)),
        ]
        saved = [(obj, name, getattr(obj, name)) for obj, name, _ in swaps]
        jax.monitoring.register_event_duration_secs_listener(self._on_compile)
        gc.callbacks.append(self._on_gc)
        try:
            for obj, name, new in swaps:
                setattr(obj, name, new)
            yield self
        finally:
            for obj, name, old in saved:
                setattr(obj, name, old)
            jax.monitoring.unregister_event_duration_listener(
                self._on_compile)
            gc.callbacks.remove(self._on_gc)
            if self.window_open and self.trace_dir is not None:
                self._close_window(self.window_steps)
