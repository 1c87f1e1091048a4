"""From a profiler trace to device-op intervals, idle gaps and host spans.

``events_from_file`` reads the ``.xplane.pb`` the JAX profiler writes
(``jax.profiler.ProfileData``) into plain ``Event`` records: the ops that
ran on each chip (line ``XLA Ops`` of plane ``/device:TPU:<n>``) and the
benchmark's own host spans (``SPANS``, from ``TraceAnnotation``).
``reduce`` turns them into a ``Summary``: the window (first span's start to
the last span's end), each chip's busy time (the union of its op
intervals inside the window), the idle gaps of chip 0 labelled by the host
span open during each, and op time by instruction.

A TPU op's event is named by its instruction's HLO text, which
``hlo.parse_event`` reads.  Control flow (``while``, ``conditional``,
``call``) spans the ops inside it and is left out: busy time is the time in
which a leaf op ran.
"""
from __future__ import annotations

import dataclasses
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

from chipbench import hlo

SPANS = ("data", "dispatch", "sync")
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
TOP = 10
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass(frozen=True)
class Event:
    where: str            # "host" or the chip's index as a string
    name: str
    start_ns: float
    end_ns: float


def events_from_profile(profile, n_devices: int) -> List[Event]:
    out = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE):
            idx = plane.name[len(DEVICE_PLANE):]
            if not idx.isdigit() or int(idx) >= n_devices:
                continue
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out += [Event(idx, e.name, e.start_ns, e.end_ns)
                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [Event("host", e.name, e.start_ns, e.end_ns)
                        for e in line.events if e.name in SPANS]
    return out


def events_from_file(path: str, n_devices: int) -> List[Event]:
    from jax.profiler import ProfileData
    return events_from_profile(ProfileData.from_file(path), n_devices)


def union(intervals: Sequence[Tuple[float, float]]):
    """Sorted, merged intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                       # averaged over the chips
    steps: int
    chips: int
    op_s: Dict[str, float]              # by op, averaged over chips
    ops: Dict[str, List[Tuple[float, float]]]   # per chip, inside window
    instrs: Dict[str, List[hlo.Instruction]]    # per chip, parallel to ops
    gaps: List[Tuple[str, float]]       # chip 0's idle gaps, longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def of_kind(self, kind: str):
        """(seconds, instruction) of every op of ``kind`` (see
        ``hlo.event_kind``) on every chip."""
        return [((e - s) * 1e-9, ins) for chip in self.ops
                for (s, e), ins in zip(self.ops[chip], self.instrs[chip])
                if hlo.event_kind(ins) == kind]

    def kind_seconds(self, kind: str) -> float:
        """Seconds of the ops of ``kind`` in the window, averaged over the
        chips."""
        return sum(t for t, _ in self.of_kind(kind)) / self.chips

    def breakdown(self) -> dict:
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.gaps[:TOP]]}


def _label(spans, s, e):
    """The host span that overlaps [s, e] most, or 'none'."""
    best, name = 0.0, "none"
    for ss, se, n in spans:
        if se <= s:
            continue
        if ss >= e:
            break
        ov = min(e, se) - max(s, ss)
        if ov > best:
            best, name = ov, n
    return name


def reduce(events: Sequence[Event], chips: int) -> Summary:
    spans = sorted((e.start_ns, e.end_ns, e.name) for e in events
                   if e.where == "host")
    if not spans:
        raise ValueError("no host spans in the trace")
    lo = spans[0][0]
    hi = max(e for _, e, _ in spans)
    ops: Dict[str, List[Tuple[float, float]]] = {}
    instrs: Dict[str, List[hlo.Instruction]] = {}
    for e in sorted((e for e in events if e.where != "host"),
                    key=lambda e: e.start_ns):
        if e.end_ns <= lo or e.start_ns >= hi:
            continue
        ins = hlo.parse_event(e.name)
        if ins.opcode in CONTAINERS:
            continue
        ops.setdefault(e.where, []).append(
            (max(e.start_ns, lo), min(e.end_ns, hi)))
        instrs.setdefault(e.where, []).append(ins)
    if len(ops) != chips:
        raise ValueError(f"device ops on {sorted(ops)} of {chips} chips")
    busy = sum(sum(e - s for s, e in union(v)) for v in ops.values()) / chips
    op_s: Dict[str, float] = defaultdict(float)
    for dev in ops:
        for (s, e), ins in zip(ops[dev], instrs[dev]):
            op_s[f"{ins.opcode} {ins.name}".strip()] += (e - s) * 1e-9 / chips
    merged = union(ops[min(ops, key=int)])
    gaps = []
    edges = [lo] + [x for se in merged for x in se] + [hi]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e > s:
            gaps.append((_label(spans, s, e), (e - s) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9,
                   steps=sum(1 for *_, n in spans if n == "dispatch"),
                   chips=chips, op_s=dict(op_s), ops=ops, instrs=instrs,
                   gaps=gaps)


def reduce_file(path: str, chips: int) -> Summary:
    return reduce(events_from_file(path, chips), chips)
