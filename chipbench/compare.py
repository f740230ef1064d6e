"""The comparison that decides ``correct`` for a training cell.

The timed path's first steps against the reference's own trajectory from
the same weights over the same batches:

* ``loss_gap`` — the largest, over the compared steps, of
  |program loss − reference loss| / |reference loss|;
* ``grad_norm_gap`` — the first gradient as SGD received it, worked out
  from each side's own float32 weights after one step,
  ``(w0 − w1) / lr`` (in float64 on the host): the program's norm against
  the reference's, for the output layer's leaves (the model's
  ``output_leaves``).  Both sides round their weights to float32 alike, so
  the rounding, which is up to a few thousandths of a step's change, mostly
  cancels.  Only the output layer: every other weight's gradient passes a
  ReLU mask, and on a few seeds a pre-activation within rounding of zero
  falls on the other side of it in the program than in the reference,
  which moves that gradient by up to ~1e-5 with both sides sound;
* ``update_norm_gap`` — the norm of ``w_last − w0`` after the compared
  steps against the reference's, leaf by leaf.

A leaf's gap is |program norm − reference norm| over the larger of that
leaf's reference norm and the median leaf's; the worst leaf counts.  A
leaf whose reference gradient norm is under a thousandth of the median
leaf's moves by round-off alone and is left out of both norm gaps.  A
number that is not finite reads ``inf``.
"""
from __future__ import annotations

import math
import statistics

import numpy as np

#: leaves whose reference gradient norm is under this share of the median
#: leaf's are left out of the norm gaps
MOVING = 1e-3


def _norms(leaves) -> list:
    return [float(np.linalg.norm(np.asarray(a, np.float64))) for a in leaves]


def _worst_gap(got, want, keep, pick) -> float:
    """Worst gap over the ``pick``ed leaves; the median is of the moving
    (``keep``) leaves."""
    med = statistics.median(w for w, k in zip(want, keep) if k)
    worst = max((abs(g - w) / max(w, med)
                 for g, w, p in zip(got, want, pick) if p), default=0.0)
    return worst if math.isfinite(worst) else math.inf


def numbers(prog: dict, ref: dict, lr: float, output: list) -> dict:
    """``prog``: ``losses`` of the compared steps and host leaves ``w0``,
    ``w1`` (after the first step) and ``w_last``; ``ref``: the output of
    :func:`chipbench.reference.sgd_steps` over the same batches;
    ``output``: which leaves are the output layer's."""
    gaps = [abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                               ref["losses"])]
    loss_gap = max(gaps) if all(map(math.isfinite, gaps)) else math.inf
    exact = _norms(ref["grads1"])
    med = statistics.median(exact)
    keep = [g >= MOVING * med for g in exact]
    w0 = [np.asarray(w, np.float64) for w in prog["w0"]]

    def grad(w1):
        return _norms([(a - np.asarray(b, np.float64)) / lr
                       for a, b in zip(w0, w1)])

    grad_prog, grad_ref = grad(prog["w1"]), grad(ref["params"][0])
    last = [k and o for k, o in zip(keep, output)]
    upd_prog = _norms([np.asarray(b, np.float64) - a
                       for a, b in zip(w0, prog["w_last"])])
    upd_ref = _norms([np.asarray(b, np.float64) - a
                      for a, b in zip(w0, ref["params"][-1])])
    return {"loss_gap": loss_gap,
            "grad_norm_gap": _worst_gap(grad_prog, grad_ref, keep, last),
            "update_norm_gap": _worst_gap(upd_prog, upd_ref, keep, keep)}


def judge(values: dict, limits: dict):
    """``(correct, checks)``: every number at or under its limit;
    ``checks`` maps each name to its value and limit."""
    checks = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
