"""Faults planted under the timed path of a fit, each one that the check
has to catch: the fit goes through the same call as in the window, and the
program underneath returns a wrong answer.

  * ``step_returns_state_unchanged``  CG runs no step: alpha stays at its
    start, 0, so the predictions are 0 and ``pred_gap`` reads exactly 1;
  * ``half_rows_left_out``            the solver sees the first half of
    the rows only;
  * ``answer_altered``                the largest entry of alpha changes
    sign where the solver returns it.

One chip per cell, so there is no exchange between chips to leave out.
``planted(name)`` patches the program for the length of a ``with`` block;
the CG fault takes effect only in programs traced inside it.
"""
from __future__ import annotations

import contextlib
import dataclasses

import numpy as np

import repro.api.estimators as estimators
import repro.core.falkon as falkon


def _no_step(cg):
    return lambda matvec, b, iters, **kw: cg(matvec, b, 0, **kw)


def _half_rows(fit):
    def broken(kernel, x, y, *a, **kw):
        h = x.shape[0] // 2
        return fit(kernel, x[:h], y[:h], *a, **kw)
    return broken


def _altered(fit):
    def broken(*a, **kw):
        model = fit(*a, **kw)
        i = int(np.argmax(np.abs(np.asarray(model.alpha))))
        return dataclasses.replace(model, alpha=model.alpha.at[i].multiply(-1.0))
    return broken


FAULTS = {
    "step_returns_state_unchanged": (falkon, "cg", _no_step),
    "half_rows_left_out": (estimators, "falkon_fit", _half_rows),
    "answer_altered": (estimators, "falkon_fit", _altered),
}


@contextlib.contextmanager
def planted(name: str):
    owner, attr, make = FAULTS[name]
    real = getattr(owner, attr)
    setattr(owner, attr, make(real))
    try:
        yield
    finally:
        setattr(owner, attr, real)
