"""The comparison that decides ``correct`` for a training cell.

Three numbers compare the program's first steps with the reference's, on
the same weights and batches:

- ``loss_gap``: the largest relative gap of a step's loss;
- ``grad_gap``: the first gradient as the optimizer gets it (clipped),
  read from Adam's first moment after step 1 as ``m / (1 - b1)``: for the
  worst leaf, the gap between the program's norm and the reference's,
  over the larger of that leaf's reference norm and the median leaf's;
- ``change_gap``: the parameters' change over the compared steps, read
  before the next step runs, by the same worst-leaf measure. Leaves whose
  reference gradient is under a thousandth of the median leaf's (a key's
  bias, under softmax) move by round-off alone and are left out.

Where the program holds a replica of the state on each chip, every
replica is compared, so a replica that drifted from the others fails.
"""
from __future__ import annotations

from typing import Dict, Mapping, Sequence, Tuple

import numpy as np

#: a leaf counts in the change only where its reference gradient is at
#: least this share of the median leaf's
MOVED_SHARE = 1e-3
NUMBERS = ("loss_gap", "grad_gap", "change_gap")


def _worst(prog: np.ndarray, ref: np.ndarray, names: Sequence[str],
           keep: np.ndarray) -> Tuple[float, str]:
    prog = np.atleast_2d(np.asarray(prog, np.float64))
    ref = np.asarray(ref, np.float64)
    floor = np.median(ref[keep]) if keep.any() else 0.0
    den = np.maximum(ref, floor)
    gap = np.abs(prog - ref[None]) / np.where(den > 0, den, 1.0)
    gap = np.where(np.isfinite(prog), gap, np.inf)[:, keep]
    if gap.size == 0:
        return 0.0, ""
    r, i = np.unravel_index(int(np.argmax(gap)), gap.shape)
    return float(gap[r, i]), f"{np.asarray(names)[keep][i]}@{r}"


def gaps(prog: Mapping, ref: Mapping) -> Dict[str, Tuple[float, str]]:
    """``prog``: ``loss`` (steps,), ``grad_norm`` and ``change_norm``
    (replicas, leaves); ``ref``: ``loss``, ``grad_norm`` and
    ``change_norm`` (leaves), and ``names``. Returns each number with
    the step or leaf (and replica) it was read at."""
    lp = np.asarray(prog["loss"], np.float64)
    lr = np.asarray(ref["loss"], np.float64)
    lg = np.abs(lp - lr) / np.abs(lr)
    lg = np.where(np.isfinite(lp), lg, np.inf)
    names = ref["names"]
    gr = np.asarray(ref["grad_norm"], np.float64)
    every = np.ones(gr.shape, bool)
    moved = gr >= MOVED_SHARE * np.median(gr)
    return {
        "loss_gap": (float(lg.max()), f"step{int(np.argmax(lg)) + 1}"),
        "grad_gap": _worst(prog["grad_norm"], gr, names, every),
        "change_gap": _worst(prog["change_norm"], ref["change_norm"], names,
                             moved),
    }


def decide(found: Mapping[str, Tuple[float, str]], limits: Mapping
           ) -> Tuple[bool, Dict[str, dict]]:
    """(correct, {number: {"value", "limit", "at"}}); a number that is
    not finite, or over its limit, makes the run incorrect."""
    out, ok = {}, True
    for k in NUMBERS:
        v, at = found[k]
        lim = float(limits[k]["limit"])
        ok = ok and bool(np.isfinite(v)) and v <= lim
        out[k] = {"value": v, "limit": lim, "at": at}
    return ok, out
