"""Faults planted under the timed path, to show that ``correct`` catches them.

Each fault is a context manager that patches the system under test while it
is active.  ``chipbench/calibrate.py --fault`` reads them on the chip at a
cell's own size; ``chipbench/tests`` drives whole runs through them on the
CPU and expects ``correct`` to come out false.

* ``unchanged`` (train) — the train step returns its parameters and optimizer
  state as it got them.
* ``half`` (train) — the train step sees only the first half of the batch's
  rows and takes the mean over them.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patch(obj, name, new):
    old = getattr(obj, name)
    setattr(obj, name, new)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _train_step_patch(wrap):
    from repro.msl import pipeline

    make = pipeline.make_pipeline_train_step

    def patched(*args, **kwargs):
        return wrap(make(*args, **kwargs))

    return _patch(pipeline, "make_pipeline_train_step", patched)


def train_unchanged():
    def wrap(step):
        def same(params, opt_state, batch):
            _, _, metrics = step(params, opt_state, batch)
            return params, opt_state, metrics
        return same

    return _train_step_patch(wrap)


def train_half():
    def wrap(step):
        def half(params, opt_state, batch):
            n = batch["tokens"].shape[0] // 2
            return step(params, opt_state,
                        {k: v[:n] for k, v in batch.items()})
        return half

    return _train_step_patch(wrap)


FAULTS = {
    "train": {"unchanged": train_unchanged, "half": train_half},
}
