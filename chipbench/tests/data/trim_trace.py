"""Cut a recorded ``.xplane.pb`` down to the first steps of its window, as
an XSpace text proto small enough to keep as test data.

    python3 chipbench/tests/data/trim_trace.py <trace.xplane.pb> <out> \
        --chips 1 --steps 1

Keeps the benchmark's host spans of the first ``--steps`` steps (each
``data``, ``dispatch`` and ``sync``) and the ops of the ``XLA Ops`` line of
each chip that overlap them; everything else goes.  An op keeps of its
HLO text what ``hlo.parse_event`` reads: name, result type, opcode, custom
call target and called computation.
``jax.profiler.ProfileData.from_text_proto`` reads the result back.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))

from chipbench import hlo, trace  # noqa: E402


def short(text):
    ins = hlo.parse_event(text)
    if not ins.opcode:
        return text
    out = f"%{ins.name} = {ins.result} {ins.opcode}(...)"
    if ins.target:
        out += f', custom_call_target="{ins.target}"'
    if ins.calls:
        out += f", calls=%{ins.calls}"
    return out


def trim(events, steps):
    spans = sorted((e for e in events if e.where == "host"),
                   key=lambda e: e.start_ns)
    starts = [e.start_ns for e in spans if e.name == "data"]
    if len(starts) <= steps:
        raise SystemExit(f"the trace has {len(starts)} steps, fewer than "
                         f"{steps} + 1")
    lo, hi = starts[0], starts[steps]
    keep = [e for e in spans if lo <= e.start_ns < hi]
    keep += [trace.Event(e.where, short(e.name), e.start_ns, e.end_ns)
             for e in events if e.where != "host"
             and e.end_ns > lo and e.start_ns < hi]
    return keep


def text_proto(events) -> str:
    base = min(e.start_ns for e in events)
    planes = {}
    for e in events:
        plane = ("/host:CPU" if e.where == "host"
                 else f"{trace.DEVICE_PLANE}{e.where}")
        line = "python" if e.where == "host" else trace.OPS_LINE
        planes.setdefault(plane, {}).setdefault(line, []).append(e)
    out = []
    for pid, (plane, lines) in enumerate(sorted(planes.items()), 1):
        names = sorted({e.name for evs in lines.values() for e in evs})
        meta = {n: i for i, n in enumerate(names, 1)}
        out.append(f'planes {{\n  id: {pid}\n  name: "{plane}"')
        for lid, (line, evs) in enumerate(sorted(lines.items()), 1):
            out.append(f'  lines {{\n    id: {lid}\n    name: "{line}"\n'
                       f'    timestamp_ns: {int(base)}')
            for e in sorted(evs, key=lambda e: e.start_ns):
                off = round((e.start_ns - base) * 1000)
                dur = round((e.end_ns - e.start_ns) * 1000)
                out.append(f"    events {{ metadata_id: {meta[e.name]} "
                           f"offset_ps: {off} duration_ps: {dur} }}")
            out.append("  }")
        for n, i in meta.items():
            quoted = n.replace("\\", "\\\\").replace('"', '\\"')
            out.append(f'  event_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{quoted}" }} }}')
        out.append("}")
    return "\n".join(out) + "\n"


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("out")
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--steps", type=int, default=1)
    args = ap.parse_args()
    events = trace.events_from_file(args.trace, args.chips)
    with open(args.out, "w") as f:
        f.write(text_proto(trim(events, args.steps)))


if __name__ == "__main__":
    main()
