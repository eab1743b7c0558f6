"""The comparison that decides ``correct`` for a training cell.

Three numbers, each with its own limit (a configuration's ``limits``):

``loss_rel``     the largest relative gap between the program's loss and the
                 reference's over the first three steps;
``grad_leaf``    the first gradient as the optimizer gets it (its first
                 moment after one step, rescaled), by the worst leaf: the gap
                 between the program's norm and the reference's norm of that
                 leaf, against the reference's norm of that leaf or of the
                 median leaf, whichever is larger (some gradients are all but
                 zero);
``dparam_leaf``  the parameters' change after the three steps, likewise, over
                 the leaves whose first gradient is alive: where the
                 reference's gradient norm is under ``DEAD`` of the median
                 leaf's (a key bias, which softmax cancels), an adaptive
                 optimizer turns rounding noise into full-sized steps, and
                 the change says nothing about the step.
"""

from __future__ import annotations

import math
import statistics

NUMBERS = ("loss_rel", "grad_leaf", "dparam_leaf")
DEAD = 1e-3


def worst_leaf(program: dict, reference: dict):
    """(gap, path) of the leaf whose norms differ most."""
    if set(program) != set(reference):
        odd = sorted(set(program) ^ set(reference))[:4]
        raise ValueError(f"program and reference disagree on leaves: {odd}")
    floor = statistics.median(reference.values())
    worst, where = 0.0, None
    for path, ref in reference.items():
        gap = abs(program[path] - ref) / max(ref, floor, 1e-30)
        if not gap <= worst:  # a NaN wins
            worst, where = gap, path
    return worst, where


def readings(program: dict, reference: dict) -> dict:
    """The numbers compared, from both sides' readings of the three steps."""
    loss = max(abs(p - r) / abs(r)
               for p, r in zip(program["loss"], reference["loss"]))
    grad, grad_at = worst_leaf(program["moment_norms"],
                               reference["moment_norms"])
    alive_from = DEAD * statistics.median(reference["moment_norms"].values())
    alive = [k for k, v in reference["moment_norms"].items()
             if v >= alive_from]
    dpar, dpar_at = worst_leaf({k: program["dparam_norms"][k] for k in alive},
                               {k: reference["dparam_norms"][k] for k in alive})
    return {"loss_rel": loss, "grad_leaf": grad, "dparam_leaf": dpar,
            "grad_leaf_at": grad_at, "dparam_leaf_at": dpar_at}


def judge(values: dict, limits: dict):
    """(ok, rows): each number beside its limit."""
    rows, ok = [], True
    for name in NUMBERS:
        value, limit = values[name], limits[name]
        passed = math.isfinite(value) and value <= limit
        ok = ok and passed
        rows.append({"number": name, "value": value, "limit": limit,
                     "ok": passed})
    return ok, rows
