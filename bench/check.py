"""The comparison that decides ``correct``: the program's round 0 against the
plain reference's (``bench/reference``).

The numbers below; those that ``bench/limits/<cell>.json`` gives a limit
are compared:

* ``first_loss_gap``: the relative gap of the first inner step's train loss
  (mean over workers): the forward pass alone, before any update;
* ``loss_gap``: the largest relative gap of a step's train loss (mean over
  workers) over the H inner steps of round 0;
* ``eval_gap``: the relative gap of the in-program eval loss after the sync;
* ``grad_gap``: the outer momentum after round 0 is eta_out times the
  pseudogradient the outer optimizer received; per leaf, the gap between the
  program's norm and the reference's, over the larger of the reference's norm
  of that leaf and of the median leaf; the worst leaf;
* ``change_gap``: the same for the outer parameters' change over round 0.

Leaves whose first gradient in the reference is under a thousandth of the
median leaf's move by round-off alone and are left out of the two leaf
numbers.
"""
from __future__ import annotations

import json
import math
import os

import numpy as np

NUMBERS = ("first_loss_gap", "loss_gap", "eval_gap", "grad_gap", "change_gap")
STILL_GRAD = 1e-3


def moved_leaves(ref: dict) -> list:
    g = ref["grad1"]
    med = float(np.median(list(g.values())))
    return sorted(k for k, v in g.items() if v >= STILL_GRAD * med)


def leaf_gaps(prog: dict, ref: dict, keep: list) -> dict:
    """{leaf: gap of the program's norm from the reference's, over the larger
    of the reference's norm of that leaf and of the median leaf}."""
    missing = [k for k in keep if k not in prog]
    if missing:
        raise KeyError(f"program state lacks leaves {missing}")
    med = float(np.median([ref[k] for k in keep]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep}


def readings(prog: dict, ref: dict) -> dict:
    keep = moved_leaves(ref)
    loss = max(abs(p - r) / abs(r) for p, r in zip(prog["loss"], ref["loss"], strict=True))
    return {
        "first_loss_gap": abs(prog["loss"][0] - ref["loss"][0]) / abs(ref["loss"][0]),
        "loss_gap": loss,
        "eval_gap": abs(prog["eval_loss"] - ref["eval_loss"]) / abs(ref["eval_loss"]),
        "grad_gap": max(leaf_gaps(prog["u"], ref["u"], keep).values()),
        "change_gap": max(leaf_gaps(prog["change"], ref["change"], keep).values()),
    }


def load_limits(bench_dir: str, cell: str) -> dict:
    with open(os.path.join(bench_dir, "limits", f"{cell}.json")) as f:
        return {k: float(v["limit"]) for k, v in json.load(f).items()}


def judge(read: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {number: [reading, limit]}); a non-finite reading fails."""
    table = {k: [read[k], limits[k]] for k in NUMBERS if k in limits}
    ok = all(math.isfinite(v) and v <= lim for v, lim in table.values())
    return ok, table
