"""The comparison that decides `correct`: what the program's checked steps
left behind against the plain reference from the same seed and rows.

Three numbers, each with a limit of its own (``bench/limits/<cell>.json``):

  loss_gap    worst relative gap of a checked step's loss
  grad_gap    worst leaf: |program's norm - reference's norm| of the first
              clipped gradient, over the larger of the reference's norm of
              that leaf and of the median leaf
  update_gap  the same for the change of the weights over the checked
              steps; leaves whose reference gradient is under a thousandth
              of the median leaf's move by round-off alone and are left out

A number that is not finite is a failure.
"""
from __future__ import annotations

import math

import numpy as np

NAMES = ("loss_gap", "grad_gap", "update_gap")
QUIET_LEAF = 1e-3


def _leaf_gap(got, want, keep):
    got, want = np.asarray(got, np.float64)[keep], np.asarray(want, np.float64)[keep]
    floor = np.maximum(want, np.median(want))
    gap = np.abs(got - want) / floor
    i = int(np.argmax(np.where(np.isfinite(gap), gap, np.inf)))
    return float(gap[i]) if np.isfinite(gap).all() else math.inf, int(np.flatnonzero(keep)[i])


def readings(obs, ref) -> dict:
    """{name: (value, worst leaf index or None)} of ``obs`` against ``ref``
    (both ``train.Observed``)."""
    lo, lr = np.asarray(obs.losses, np.float64), np.asarray(ref.losses, np.float64)
    loss = np.abs(lo - lr) / np.abs(lr)
    loss_gap = float(loss.max()) if np.isfinite(loss).all() else math.inf
    g_ref = np.asarray(ref.grad_norms, np.float64)
    everything = np.ones(g_ref.shape, bool)
    moving = g_ref >= QUIET_LEAF * np.median(g_ref)
    return {
        "loss_gap": (loss_gap, int(np.argmax(loss)) if np.isfinite(loss).all() else None),
        "grad_gap": _leaf_gap(obs.grad_norms, g_ref, everything),
        "update_gap": _leaf_gap(obs.change_norms, ref.change_norms, moving),
    }


def judge(values: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}) for every number compared."""
    table = {n: {"value": values[n][0], "limit": limits[n]} for n in NAMES}
    ok = all(math.isfinite(t["value"]) and t["value"] <= t["limit"] for t in table.values())
    return ok, table
