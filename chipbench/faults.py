"""Faults planted under the timed path, for the check that ``correct``
catches them.  Each is a context manager that swaps one name of the program
for a broken version and puts it back.  Used by the fault tests (on the CPU,
at the smoke size) and by ``readings.py`` (on the chip, at the cell's size).

- ``unchanged_state``: the step returns its parameters and optimizer state
  as it got them (the loss is computed as usual);
- ``half_batch``: the step sees only the first half of the batch's rows and
  takes the mean over those;
- ``altered_loss``: the loss is altered by 1 % where the step produces it;
- ``altered_checkpoint``: one value of the saved state is altered where the
  save's payload is produced.

A fault that leaves out the exchange between chips has no place in a
one-chip cell.
"""
from __future__ import annotations

import contextlib

import jax
import numpy as np


@contextlib.contextmanager
def _swap(obj, name, new):
    old = getattr(obj, name)
    setattr(obj, name, new)
    try:
        yield
    finally:
        setattr(obj, name, old)


def unchanged_state():
    import repro.launch.steps as S
    import repro.launch.train as T

    def jit_train_step(cfg, run):
        step = jax.jit(S.make_train_step(cfg, T.train_settings(run)))

        def frozen(params, opt_state, batch, i):
            _, _, loss = step(params, opt_state, batch, i)
            return params, opt_state, loss

        return frozen

    return _swap(T, "jit_train_step", jit_train_step)


def _wrap_step(change):
    import repro.launch.steps as S
    make = S.make_train_step

    def make_train_step(cfg, settings, rules=None):
        return change(make(cfg, settings, rules))

    return _swap(S, "make_train_step", make_train_step)


def half_batch():
    def change(step):
        def train_step(params, opt_state, batch, i):
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return step(params, opt_state, half, i)
        return train_step
    return _wrap_step(change)


def altered_loss():
    def change(step):
        def train_step(params, opt_state, batch, i):
            p, o, loss = step(params, opt_state, batch, i)
            return p, o, loss * 1.01
        return train_step
    return _wrap_step(change)


def altered_checkpoint():
    import repro.ckpt.shards as shards
    flatten = shards._flatten

    def _flatten(tree):
        flat = flatten(tree)
        key = sorted(flat)[0]
        arr = np.array(flat[key], copy=True)
        arr.flat[0] = np.nextafter(arr.flat[0], np.inf)
        flat[key] = arr
        return flat

    return _swap(shards, "_flatten", _flatten)


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch,
          "altered_loss": altered_loss,
          "altered_checkpoint": altered_checkpoint}
