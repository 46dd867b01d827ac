"""Faults planted in the timed path, for the tests and
``tools/control.py``: each wraps the fused decode round under the
scheduler (``VStep.round``), and a run with any of them has to come out
not correct. ``FAULTS[name](sched)`` plants one after the program is
built (``harness.run_cell(patch=...)``).

On one chip there is no exchange between chips to leave out, and a
served model has no batch mean to take over half of it, so those faults
of the contract do not apply here.
"""
from __future__ import annotations

import jax.numpy as jnp


def _wrap(sched, alter):
    vs = sched.executor.vstep
    step = vs.round

    def round_(state, toks, valid):
        return alter(state, *step(state, toks, valid))

    vs.round = round_


def _other(tok):
    """A token that is not ``tok`` and still lies in the vocabulary."""
    return jnp.where(tok > 0, tok - 1, 1)


def state_unchanged(sched):
    """The round returns the state it was given: no KV is written."""
    _wrap(sched, lambda old, new, nxt, last: (old, nxt, last))


def token_altered(sched):
    """Every slot's token is altered where the round produces it."""
    _wrap(sched, lambda old, new, nxt, last: (new, _other(nxt), last))


def one_slot_token_altered(sched):
    """Only the middle slot's token is altered: the check has to compare
    every slot to see it."""
    k = sched.executor.n_slots // 2
    _wrap(sched, lambda old, new, nxt, last:
          (new, nxt.at[k].set(_other(nxt[k])), last))


FAULTS = {f.__name__: f for f in (state_unchanged, token_altered,
                                  one_slot_token_altered)}
