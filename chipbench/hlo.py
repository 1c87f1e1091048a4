"""The HLO instruction behind a device-trace op: ``parse_event`` reads an
op's event, which is named by its instruction's HLO text."""
from __future__ import annotations

import dataclasses
import re

COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute")


@dataclasses.dataclass(frozen=True)
class Instruction:
    name: str
    opcode: str
    result: str            # the result type, e.g. 'bf16[16,16,256,72]{...}'
    target: str            # custom_call_target, or ''
    calls: str             # the computation a fusion calls, or ''


def shape_of(ty: str):
    """'bf16[16,16,256,72]{...}' -> ('bf16', (16, 16, 256, 72))."""
    m = re.match(r"([a-z0-9]+)\[([0-9,]*)\]", ty)
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    return m.group(1), dims


_EVENT = re.compile(r"^%([\w.\-]+)\s*=\s*(.*?)\s([a-z][a-z0-9\-]*)\((.*)$",
                    re.S)


def parse_event(text: str) -> Instruction:
    """The instruction behind a device-trace op, whose event name is the
    instruction's HLO text; a bare name gives an empty opcode."""
    m = _EVENT.match(text)
    if not m:
        return Instruction(text, "", "", "", "")
    name, result, opcode, rest = m.groups()
    t = re.search(r'custom_call_target="([^"]+)"', rest)
    c = re.search(r"calls=%?([\w.\-]+)", rest)
    return Instruction(name, opcode, result, t.group(1) if t else "",
                       c.group(1) if c else "")


def event_kind(ins: Instruction) -> str:
    """'pallas' for a Pallas TPU kernel, the collective an op runs (by its
    opcode, or by the name of the computation a fusion calls), else ''."""
    if ins.opcode == "custom-call" and ins.target == "tpu_custom_call":
        return "pallas"
    op = re.sub(r"-(start|done)$", "", ins.opcode)
    if op in COLLECTIVES:
        return op
    if ins.opcode == "fusion":
        for kind in COLLECTIVES:
            if kind in ins.calls:
                return kind
    return ""
