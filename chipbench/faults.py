"""Faults planted underneath the timed path, for the control and the fault
tests. Each is a context manager that patches the program for the length
of a run and restores it after.

- ``cost_blind``: the control. The Sinkhorn plan handed to the host's
  rounding is uniform over each job's allowed arcs, so the configuration's
  third guarantee (the decision is the Eq 8 optimum) breaks while every
  placement stays feasible.
- ``altered_answer``: one job's column is moved to an arc the mask forbids
  after the host has rounded it.
- ``half_batch``: the second half of every answer is left unassigned.
- ``unchanged_state``: the scheduler returns every job it was given,
  placing none.
- ``stale_plan``: the host rounds the previous solve's plan in place of
  its own: the first ``min(M, M')`` rows of that plan, each row past them
  uniform over its allowed arcs. It fires on every solve but the first.
  Where several fleets share a run, the previous solve is as a rule
  another fleet's: the fault that an exchange between fleets, or between
  the chips that batch their solves, can have.

``late_dispatch`` is not among ``FAULTS``: it leaves every answer as the
reference would give it and moves ``failed``, not ``correct``. It starts
the first job placed at or after a decision instant at its deadline, so a
job that could have run on time finishes late.
"""
from __future__ import annotations

import contextlib

import numpy as np


@contextlib.contextmanager
def _patch(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _wrap_finalize(after=None, before=None):
    from repro.core.solvers import jax_solver
    orig = jax_solver._finalize

    def finalize(X, Cn, c_eff, mask, cap, soften, overrun, tol):
        if before is not None:
            X = before(X, mask)
        res = orig(X, Cn, c_eff, mask, cap, soften, overrun, tol)
        return res if after is None else after(res, mask)

    return _patch(jax_solver, "_finalize", finalize)


def cost_blind():
    def uniform(X, mask):
        m = np.asarray(mask, np.float64)
        return m / np.maximum(m.sum(axis=1, keepdims=True), 1.0)
    return _wrap_finalize(before=uniform)


def altered_answer():
    def alter(res, mask):
        bad = np.argwhere(~np.asarray(mask, bool))
        if len(bad) and (res.assign >= 0).all():
            m, c = bad[0]
            res.assign[m] = c
        return res
    return _wrap_finalize(after=alter)


def half_batch():
    def halve(res, mask):
        res.assign[len(res.assign) // 2:] = -1
        return res
    return _wrap_finalize(after=halve)


def stale_plan():
    last = []

    def stale(X, mask):
        prev, last[:] = (last[0] if last else None), [X]
        if prev is None:
            return X
        m = np.asarray(mask, np.float64)
        out = m / np.maximum(m.sum(axis=1, keepdims=True), 1.0)
        rows, cols = min(len(X), len(prev)), min(X.shape[1], prev.shape[1])
        out[:rows] = 0.0
        out[:rows, :cols] = prev[:rows, :cols]
        return out
    return _wrap_finalize(before=stale)


def unchanged_state():
    from repro.policy import pipeline

    def schedule(self, jobs, now_s, capacity):
        return pipeline.Decision([], np.zeros(0, np.int64), list(jobs), None,
                                 False)
    return _patch(pipeline.PolicyPipeline, "schedule", schedule)


def late_dispatch(at_s: float):
    from chipbench.harness import deadline_s
    from repro.policy import pipeline
    schedule = pipeline.PolicyPipeline.schedule
    delayed = []

    def delay_one(self, jobs, now_s, capacity):
        dec = schedule(self, jobs, now_s, capacity)
        if not delayed and now_s >= at_s and dec.scheduled:
            job = dec.scheduled[0]
            job.planned_start_s = deadline_s(job)
            delayed.append(job)
        return dec
    return _patch(pipeline.PolicyPipeline, "schedule", delay_one)


FAULTS = dict(cost_blind=cost_blind, altered_answer=altered_answer,
              half_batch=half_batch, unchanged_state=unchanged_state,
              stale_plan=stale_plan)
