"""Faults planted under the timed path, and the controls, for the tests and
for :mod:`chipbench.readings`.  The benchmark's own runs never use them.

Each ``step_hook`` takes the place of the engine's step inside the
Trainer's own loop (see :class:`chipbench.train.TrainCell`):

* ``state_unchanged`` — the engine's step runs, its new weights are thrown
  away;
* ``reference(precision, keep)`` — the plain reference put in the
  program's place: at a lower precision it is the control; with
  ``keep=0.5`` it leaves half of the batch out and takes the mean over the
  rest.

:func:`drop_exchange` leaves the exchange between chips out: every
``ppermute`` delivers zeros.
"""
from __future__ import annotations

import contextlib


def state_unchanged(cell, params, batch):
    _, loss = cell.trainer.bundle.__class__.train_step(
        cell.trainer.bundle, params, batch)
    return params, loss


def reference(precision: str = "highest", keep: float = 1.0):
    from chipbench import reference as ref

    def hook(cell, params, batch):
        b = cell.replay.batch_at(cell.steps_done)
        ws = [p["w"] for p in params]
        loss, grads = ref.loss_and_grad(ws, b, cell.data, precision, keep)
        return [{"w": w - cell.lr * g} for w, g in zip(ws, grads)], loss

    return hook


@contextlib.contextmanager
def drop_exchange():
    """Within the block, every ``jax.lax.ppermute`` traced returns zeros."""
    import jax
    import jax.numpy as jnp

    orig = jax.lax.ppermute
    jax.lax.ppermute = lambda x, *a, **k: jnp.zeros_like(x)
    try:
        yield
    finally:
        jax.lax.ppermute = orig
