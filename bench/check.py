"""The comparison that decides ``correct``.

Readings of the program's first steps against the plain reference's, each
a gap (0 where the two agree).  A workload's ``limits`` name the numbers
its cell is held to, each with its limit:

- ``loss_gap``: the largest, over the checked steps, of |loss - ref| / ref,
  the loss being the mean over data peers of each peer's mean token loss;
  ``loss0_gap``: the same for the first step alone, before any sync acts;
- ``grad_norm_gap``: by the worst leaf, the gap between the norms of the
  first step's gradient as the optimizer received it (read back from its
  state after that step) and the reference's exact-mean gradient;
  ``grad_norm_median_gap``: the median leaf's gap;
- ``sync_in_grad_gap``: by the worst leaf, the same gap for that gradient
  plus the data peers' mean EF residual after the first step (cells with
  error feedback): for a sync that quantizes each peer's gradient once and
  keeps all of its error in the residual (faithful), the mean gradient the
  sync received, with no codec error left in it;
- ``update_norm_gap``: by the worst leaf, the gap between the norms of the
  parameters' change over the checked steps, as the next step would
  receive them; ``update_norm_median_gap``: the median leaf's gap;
- ``replica_gap``: the largest difference between the copies that
  different chips hold of one replicated parameter after the checked
  steps (exact: every sync mode leaves all peers with identical bytes).

A gap of norms is measured against the reference's norm of that leaf or
of the median leaf, whichever is larger.  Leaves whose reference gradient
norm is under a thousandth of the median leaf's move by round-off alone
and are not counted.
"""
from __future__ import annotations

import math

import numpy as np

NUMBERS = ("loss_gap", "loss0_gap", "grad_norm_gap", "grad_norm_median_gap",
           "sync_in_grad_gap", "update_norm_gap", "update_norm_median_gap", "replica_gap")
NEGLIGIBLE = 1e-3


def counted(ref_grad_norms) -> np.ndarray:
    g = np.asarray(ref_grad_norms, np.float64)
    return g >= NEGLIGIBLE * np.median(g)


def leaf_gaps(prog, ref, mask) -> np.ndarray:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    floor = np.median(ref[mask])
    return (np.abs(prog - ref) / np.maximum(ref, floor))[mask]


def readings(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: dicts of ``losses``, ``grad_norms``, ``update_norms``."""
    mask = counted(ref["grad_norms"])
    lp, lr = np.asarray(prog["losses"], np.float64), np.asarray(ref["losses"], np.float64)
    n = min(len(lp), len(lr))
    loss = np.abs(lp[:n] - lr[:n]) / np.abs(lr[:n])
    grad = leaf_gaps(prog["grad_norms"], ref["grad_norms"], mask)
    upd = leaf_gaps(prog["update_norms"], ref["update_norms"], mask)
    out = {
        "loss_gap": float(np.max(loss)), "loss0_gap": float(loss[0]),
        "grad_norm_gap": float(np.max(grad)), "grad_norm_median_gap": float(np.median(grad)),
        "update_norm_gap": float(np.max(upd)), "update_norm_median_gap": float(np.median(upd)),
    }
    if "sync_in_norms" in prog:
        out["sync_in_grad_gap"] = float(np.max(leaf_gaps(prog["sync_in_norms"],
                                                         ref["grad_norms"], mask)))
    if "replica_gap" in prog:
        out["replica_gap"] = float(prog["replica_gap"])
    return out


def worst_leaves(prog: dict, ref: dict, paths, key: str, k: int = 3) -> list:
    """The ``k`` leaves with the largest gap of ``key`` norms (for the log)."""
    mask = counted(ref["grad_norms"])
    p, r = np.asarray(prog[key], np.float64), np.asarray(ref[key], np.float64)
    floor = np.median(r[mask])
    gaps = np.where(mask, np.abs(p - r) / np.maximum(r, floor), -1.0)
    order = np.argsort(-gaps)[:k]
    return [[paths[i], float(p[i]), float(r[i]), float(gaps[i])] for i in order]


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})`` over the numbers ``limits`` names:
    every one read, finite and within its limit.  No limits, no verdict: False."""
    out, ok = {}, bool(limits)
    for name, lim in limits.items():
        if name not in NUMBERS:
            raise KeyError(f"unknown number {name!r} in limits; known: {NUMBERS}")
        v = values.get(name)
        ok = ok and v is not None and math.isfinite(v) and v <= lim
        out[name] = {"value": v, "limit": lim}
    return ok, out
