"""The collectives of a ``--trace 1`` run on a mesh, split the way the
four-chip cell's readers read them (ops, and their time a step, from
``chipbench.scopes``).

- Planned switches: the all-to-alls that the DSP schedule plans
  (``core/schedule.py``): under ``dsp_switch`` in the forward and remat's
  recompute, and, in the backward, on the block-end anchors, whose
  innermost scope is ``spatial`` or ``temporal``.
- ZeRO's: every other collective.  In the 3B cell these are the weight
  gathers, the gradient reduce-scatters and the small reductions of the
  adaLN and embedding gradients; no all-to-all there is unplanned (the
  720M step on a mesh has some, under ``mlp``: a cell of it would need
  its own split).  The partitioner names a weight gather after the dot it
  feeds (``mlp``, ``proj``) and a gradient reduce-scatter after the
  backward's dot, or not at all: no collective carries the program's
  ``zero`` scope, so they are read by kind, as what is not a planned
  switch.

Planned switches and ZeRO's together are every collective of the trace.
"""
from __future__ import annotations

import re
from typing import Optional

from chipbench import hlo, scopes, trace

ANCHORS = ("spatial", "temporal")


def innermost(op: scopes.Op) -> str:
    """The innermost of the program's scope names an op carries, or ''."""
    names = [t for t in re.split(r"[/();:]", op.op_name)
             if t in scopes.NAMES]
    return names[-1] if names else ""


def is_collective(op: scopes.Op) -> bool:
    return hlo.event_kind(op.instr) in hlo.COLLECTIVES


def is_switch(op: scopes.Op) -> bool:
    if hlo.event_kind(op.instr) != "all-to-all":
        return False
    return ("dsp_switch" in op.scopes
            or (op.backward and innermost(op) in ANCHORS))


def is_zero(op: scopes.Op) -> bool:
    return is_collective(op) and not is_switch(op)


def exposed_switch_ms_per_step(m) -> Optional[float]:
    """Of the planned switches' intervals, the milliseconds a step, per
    chip, in which no op other than a collective ran on the same chip;
    None where there is no trace with scope names or no switch."""
    path = scopes.trace_file()
    found = scopes.ops(path, m.chips) if path else []
    if not any(op.scopes for op in found) or not any(map(is_switch, found)):
        return None
    total = 0.0
    for chip in {op.chip for op in found}:
        mine = [op for op in found if op.chip == chip]
        switches = trace.union([(op.start_ns, op.end_ns)
                                for op in mine if is_switch(op)])
        compute = trace.union([(op.start_ns, op.end_ns)
                               for op in mine if not is_collective(op)])
        total += _length(switches) - _overlap(switches, compute)
    return 1e-6 * total / m.chips / m.steps


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _overlap(a, b) -> float:
    """Length of the intersection of two sorted, merged interval lists."""
    out, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        out += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out
