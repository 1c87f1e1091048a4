"""The numbers that decide ``correct`` for a training cell.

Each compares the program's first training steps with the reference's,
on the same weights and batches:

- ``loss_gap``: the relative gap between the two losses, worst over the
  checked steps.
- ``grad_gap``: the first step's gradient as the optimizer got it (its
  first moment over 1 - b1), by leaf: the gap between the program's norm
  and the reference's, over the reference's norm of that leaf or of the
  median leaf, whichever is larger; the worst leaf.
- ``update_gap``: the same for the weights' change over the checked
  steps.  Leaves whose reference gradient is under ``FLAT`` of the median
  leaf's move by round-off alone and are left out.
- ``grad_cos_gap``: 1 - cos of the angle between the program's first
  gradient and the reference's, by leaf; the worst leaf.  A norm cannot
  see a gradient that points elsewhere.
- ``update_cos_gap``: the same for the weights' change over the checked
  steps, over the leaves ``update_gap`` keeps.  AdamW's first steps move
  each weight by about the learning rate times the sign of its gradient,
  so the change's norm is all but fixed and only its direction can tell a
  wrong update.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, Iterable, Optional

import numpy as np

FLAT = 1e-3
NAMES = ("loss_gap", "grad_gap", "update_gap", "grad_cos_gap",
         "update_cos_gap")


def loss_gap(program, reference) -> float:
    worst = 0.0
    for p, r in zip(program, reference, strict=True):
        gap = abs(p - r) / abs(r)
        worst = max(worst, gap if math.isfinite(gap) else math.inf)
    return worst


def norm_gap(program_sq: Dict[str, float], reference_sq: Dict[str, float],
             keep: Optional[Iterable[str]] = None) -> float:
    """Worst leaf's |norm_program - norm_reference| over
    max(norm_reference, median leaf norm_reference)."""
    keys = sorted(reference_sq if keep is None else keep)
    if set(keys) - set(program_sq):
        raise KeyError(f"leaves missing from the program: "
                       f"{sorted(set(keys) - set(program_sq))}")
    ref = {k: math.sqrt(reference_sq[k]) for k in keys}
    med = statistics.median(ref.values())
    worst = 0.0
    for k in keys:
        p = math.sqrt(program_sq[k]) if program_sq[k] >= 0 else math.nan
        gap = abs(p - ref[k]) / max(ref[k], med)
        worst = max(worst, gap if math.isfinite(gap) else math.inf)
    return worst


def moving(reference_grad_sq: Dict[str, float]) -> list:
    norms = {k: math.sqrt(v) for k, v in reference_grad_sq.items()}
    med = statistics.median(norms.values())
    return [k for k, v in norms.items() if v >= FLAT * med]


def sums(pieces, against=None, keep=None) -> Dict[str, list]:
    """Over a run's ``pieces`` (``Reference.pieces``), brought to the host
    one at a time: by leaf path, [sum of squares, and with ``against``
    (another run's leaves on the host, in leaf order, stacked as the
    program stacks them) their sum of squares and the dot product], in
    float64.  ``keep``, a dict, receives each leaf's pieces by leaf
    index."""
    out: Dict[str, list] = {}
    for i, path, pair, a in pieces:
        a = np.asarray(a, np.float32)
        if keep is not None:
            keep.setdefault(i, []).append(a)
        r = a.astype(np.float64).ravel()
        s = out.setdefault(path, [0.0, 0.0, 0.0])
        s[0] += float(r @ r)
        if against is not None:
            p = against[i] if pair is None else against[i][pair]
            p = np.asarray(p, np.float64).ravel()
            s[1] += float(p @ p)
            s[2] += float(p @ r)
    return out


def stacked(kept: Dict[int, list], shapes) -> list:
    """``sums``' kept pieces as the leaves of ``shapes`` (a list)."""
    return [np.stack(kept[i]) if len(s.shape) > kept[i][0].ndim
            else kept[i][0] for i, s in enumerate(shapes)]


def cos_gaps(s: Dict[str, list]) -> Dict[str, float]:
    """1 - cos by leaf path from ``sums`` taken ``against`` another run."""
    out = {}
    for path, (rr, pp, pr) in s.items():
        if rr == 0.0 or pp == 0.0:
            out[path] = 0.0 if rr == pp else 1.0
        else:
            gap = 1.0 - pr / math.sqrt(rr * pp)
            out[path] = gap if math.isfinite(gap) else math.inf
    return out


def numbers(program: dict, reference: dict, cos: dict) -> Dict[str, float]:
    """``program`` and ``reference`` each hold ``losses``, ``grad_sq`` and
    ``delta_sq``; ``cos`` holds ``grad`` and ``delta``, the ``cos_gaps``
    between the two."""
    keep = moving(reference["grad_sq"])
    return {
        "loss_gap": loss_gap(program["losses"], reference["losses"]),
        "grad_gap": norm_gap(program["grad_sq"], reference["grad_sq"]),
        "update_gap": norm_gap(program["delta_sq"], reference["delta_sq"],
                               keep=keep),
        "grad_cos_gap": max(cos["grad"].values()),
        "update_cos_gap": max(cos["delta"][k] for k in keep),
    }


def checks(values: Dict[str, float], limits: Dict[str, float]) -> dict:
    """Each number beside its limit; a number passes at or under it."""
    return {k: {"value": values[k], "limit": limits[k],
                "ok": bool(values[k] <= limits[k])} for k in NAMES}
