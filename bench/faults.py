"""Faults planted under the timed path, to show that ``correct`` catches
them: each wraps the jitted train step.

- ``unchanged``: the step returns the weights and Adam's moments it was
  given (only the step counter advances).
- ``half_batch``: the step sees half of the batch (half of the sequence
  when the batch is one row), so the loss is the mean over the rest.
"""
from __future__ import annotations


def unchanged(step):
    def fn(state, x, y):
        new, metrics = step(state, x, y)
        return dict(state, step=new["step"]), metrics
    return fn


def half_batch(step):
    def fn(state, x, y):
        if x.shape[0] >= 2:
            h = x.shape[0] // 2
            return step(state, x[:h], y[:h])
        h = x.shape[1] // 2
        return step(state, x[:, :h], y[:, :h])
    return fn


FAULTS = {"unchanged": unchanged, "half_batch": half_batch}
