"""Broken versions of the timed path, to show that the check catches
them. None of them runs in a benchmark run: `control.py` runs them on the
chip at a cell's own size, and `tests/test_chipbench_faults.py` on the
CPU at a small one.

Each is a context manager that patches one driver instance for the
length of a window:

  control         breaks one guarantee the configuration states: the
                  last insert of every rank's batch acknowledged and never
                  written, the last find of every rank's batch missing an
                  acknowledged key, pops handing out values out of ticket
                  order;
  stale_state     every call returns its state unchanged;
  half_batch      half of every batch is left out (the second half of
                  each rank's ops is never issued) while its answers are
                  reported as they come;
  altered_answer  one answer of every batch is changed where it is
                  produced.

One chip has no exchange between chips, so that fault has no case here.
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp


def _alter(x):
    """x with its first element changed."""
    first = (0,) * x.ndim
    if x.dtype == jnp.bool_:
        return x.at[first].set(~x[first])
    return x.at[first].add(1)


@contextlib.contextmanager
def _patched(drv, issue=None, accept=None):
    old_issue, old_accept = drv.issue, drv.accept
    if issue is not None:
        drv.issue = lambda op, slot, **kw: issue(old_issue, op, slot, **kw)
    if accept is not None:
        drv.accept = lambda out: accept(old_accept, out)
    try:
        yield
    finally:
        drv.issue, drv.accept = old_issue, old_accept


def _last(shape):
    """The last op of every rank's batch."""
    n = jnp.arange(shape[-1])
    return jnp.broadcast_to(n == shape[-1] - 1, shape)


def control(drv):
    def issue(inner, op, slot, **kw):
        if op == "insert":
            lost = _last((drv.plan.nranks, drv.plan.batch))
            n, (state, ok, probes) = inner(op, slot, valid=~lost, **kw)
            return n, (state, ok | lost, jnp.where(lost, 1, probes))
        n, out = inner(op, slot, **kw)
        if op == "find":
            state, found, vals = out
            miss = _last(found.shape)
            return n, (state, found & ~miss,
                       jnp.where(miss[..., None], 0, vals))
        if op == "pop":
            state, got, vals = out
            return n, (state, got, jnp.flip(vals, axis=1))
        return n, out

    return _patched(drv, issue=issue)


def stale_state(drv):
    def accept(inner, out):
        answers = inner(out)
        drv.state = drv._stale
        return answers

    def issue(inner, op, slot, **kw):
        drv._stale = drv.state
        return inner(op, slot, **kw)

    return _patched(drv, issue=issue, accept=accept)


def half_batch(drv):
    def issue(inner, op, slot, **kw):
        P, n = drv.plan.nranks, drv.plan.batch
        keep = jnp.broadcast_to(jnp.arange(n) < n // 2, (P, n))
        return inner(op, slot, valid=keep, **kw)

    return _patched(drv, issue=issue)


def altered_answer(drv):
    def issue(inner, op, slot, **kw):
        n, out = inner(op, slot, **kw)
        return n, (out[0], _alter(out[1])) + tuple(out[2:])

    return _patched(drv, issue=issue)


FAULTS = {"control": control, "stale_state": stale_state,
          "half_batch": half_batch, "altered_answer": altered_answer}


def applies(name: str, pattern) -> bool:
    """Whether the fault can happen in a cell with this op pattern: a
    read-only cell has no state change to leave out."""
    if name == "stale_state":
        return any(op in ("insert", "push", "pop") for op in pattern)
    return True
