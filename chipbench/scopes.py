"""Device time by the program's scope names, from a ``--trace 1`` run.

The program names the stages of its train step with ``jax.named_scope``
(``src/repro/tracing.py``); XLA keeps the name stack in each op's
``metadata.op_name``.  On the TPU the trace carries it as the stat
``tf_op`` of each ``XLA Ops`` event's metadata (``<op_name>:<op type>``).
``ops`` reads the ``.xplane.pb`` that ``run.Context.start_trace`` writes
(once per path) and returns each device op inside the window of the
benchmark's host spans (``trace.SPANS``), clipped to it as
``trace.reduce`` clips, with the scopes its name holds and whether it runs
in the backward (a ``transpose(`` wrapper, outside remat's
``rematted_computation``).  ``ms_per_step`` sums, per chip, the ops a
reader keeps.  A trace without scope names (a program that sets none)
gives no reading.

The file is parsed with a schema built here (``XSpace``, as
``tsl/profiler/protobuf/xplane.proto`` defines it): ``ProfileData`` does
not expose event metadata's stats.  ``trim`` cuts a trace down to test
data that keeps each op's scope; ``python3 -m chipbench.scopes <trace>``
prints a run's device time by scope.
"""
from __future__ import annotations

import dataclasses
import functools
import gzip
import re
from typing import Callable, FrozenSet, List, Optional

from chipbench import hlo, trace

TF_OP = "tf_op"
NAMES = ("layers", "spatial", "temporal", "adaln", "proj", "attn", "mlp",
         "attn_bwd", "adamw", "embed", "loss", "dsp_switch")


@dataclasses.dataclass(frozen=True)
class Op:
    chip: str
    start_ns: float
    end_ns: float
    instr: hlo.Instruction
    op_name: str

    @property
    def scopes(self) -> FrozenSet[str]:
        return frozenset(t for t in re.split(r"[/();:]", self.op_name)
                         if t in NAMES)

    @property
    def backward(self) -> bool:
        return ("transpose(" in self.op_name
                and "rematted_computation" not in self.op_name)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


@functools.lru_cache(maxsize=None)
def _schema():
    """The message class of an XSpace, cut to the fields read here (the
    parser skips the others)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    fd = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="chipbench", syntax="proto3")
    F = descriptor_pb2.FieldDescriptorProto
    i64, txt, msg = F.TYPE_INT64, F.TYPE_STRING, F.TYPE_MESSAGE

    def message(name, fields, parent=fd.message_type):
        m = parent.add(name=name)
        for number, field, kind, *ref in fields:
            f = m.field.add(name=field, number=number, type=kind,
                            label=F.LABEL_REPEATED if ref[1:]
                            else F.LABEL_OPTIONAL)
            if ref:
                f.type_name = ".chipbench." + ref[0]
        return m

    message("XSpace", [(1, "planes", msg, "XPlane", "many")])
    plane = message("XPlane", [
        (1, "id", i64), (2, "name", txt), (3, "lines", msg, "XLine", "many"),
        (4, "event_metadata", msg, "XPlane.EventMetadataEntry", "many"),
        (5, "stat_metadata", msg, "XPlane.StatMetadataEntry", "many")])
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        e = message(entry, [(1, "key", i64), (2, "value", msg, value)],
                    parent=plane.nested_type)
        e.options.map_entry = True
    message("XLine", [(1, "id", i64), (2, "name", txt),
                      (3, "timestamp_ns", i64),
                      (4, "events", msg, "XEvent", "many")])
    message("XEvent", [(1, "metadata_id", i64), (2, "offset_ps", i64),
                       (3, "duration_ps", i64)])
    message("XStat", [(1, "metadata_id", i64), (5, "str_value", txt)])
    message("XEventMetadata", [(1, "id", i64), (2, "name", txt),
                               (5, "stats", msg, "XStat", "many")])
    message("XStatMetadata", [(1, "id", i64), (2, "name", txt)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench.XSpace"))


def load(path: str):
    """The XSpace of a binary ``.xplane.pb``, or of a text proto (``.gz``
    or plain) such as ``trim`` writes."""
    space = _schema()()
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        data = f.read()
    if path.endswith(".xplane.pb"):
        space.ParseFromString(data)
    else:
        from google.protobuf import text_format
        text_format.Parse(data.decode(), space)
    return space


def _device_ops(space, chips):
    """(chip, start ns, end ns, HLO text, op_name) of each event of the
    ``XLA Ops`` line of chips 0..chips-1."""
    for plane in space.planes:
        chip = plane.name[len(trace.DEVICE_PLANE):]
        if not (plane.name.startswith(trace.DEVICE_PLANE) and chip.isdigit()
                and int(chip) < chips):
            continue
        tf_op = [k for k, v in plane.stat_metadata.items() if v.name == TF_OP]
        for line in plane.lines:
            if line.name != trace.OPS_LINE:
                continue
            for e in line.events:
                md = plane.event_metadata[e.metadata_id]
                name = next((st.str_value for st in md.stats
                             if st.metadata_id in tf_op), "")
                start = line.timestamp_ns + e.offset_ps * 1e-3
                yield (chip, start, start + e.duration_ps * 1e-3, md.name,
                       name)


def _spans(space):
    """(start ns, end ns, name) of the benchmark's host spans, in order."""
    return sorted(
        (line.timestamp_ns + e.offset_ps * 1e-3,
         line.timestamp_ns + (e.offset_ps + e.duration_ps) * 1e-3,
         plane.event_metadata[e.metadata_id].name)
        for plane in space.planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if plane.event_metadata[e.metadata_id].name in trace.SPANS)


@functools.lru_cache(maxsize=None)
def ops(path: str, chips: int) -> List[Op]:
    """The device ops of chips 0..chips-1 in the window of the host spans,
    clipped to it; control flow (``trace.CONTAINERS``) left out."""
    space = load(path)
    spans = _spans(space)
    if not spans:
        raise ValueError(f"no host spans in {path}")
    lo, hi = spans[0][0], max(e for _, e, _ in spans)
    out = []
    for chip, start, end, text, name in _device_ops(space, chips):
        ins = hlo.parse_event(text)
        if end <= lo or start >= hi or ins.opcode in trace.CONTAINERS:
            continue
        out.append(Op(chip, max(start, lo), min(end, hi), ins, name))
    return out


def trace_file() -> Optional[str]:
    """The trace of this ``--trace 1`` run, found as
    ``run.Context.after_window`` finds it (which refuses a run without
    exactly one); None outside such a run."""
    from chipbench.run import TRACE_DIR, _files
    path = [f for f in _files(TRACE_DIR) if f.endswith(".xplane.pb")]
    return path[0] if len(path) == 1 else None


def ms_per_step(m, keep: Callable[[Op], bool]) -> Optional[float]:
    """Device milliseconds a step, per chip, of the ops ``keep`` accepts;
    None where there is no trace file, or it holds no scope names or no
    such op."""
    path = trace_file()
    if path is None:
        return None
    found = ops(path, m.chips)
    kept = [op for op in found if keep(op)]
    if not kept or not any(op.scopes for op in found):
        return None
    return 1e3 * sum(op.seconds for op in kept) / m.chips / m.steps


def scoped_share(found: List[Op]) -> float:
    """Share of the chips' busy time (the union of op intervals) in which
    an op that carries a scope ran."""
    busy = scoped = 0.0
    for chip in {op.chip for op in found}:
        mine = [op for op in found if op.chip == chip]
        busy += sum(e - s for s, e in trace.union(
            [(op.start_ns, op.end_ns) for op in mine]))
        scoped += sum(e - s for s, e in trace.union(
            [(op.start_ns, op.end_ns) for op in mine if op.scopes]))
    return scoped / busy


def trim(path: str, out: str, *, chips: int = 1, steps: int = 1) -> None:
    """Cut the trace at ``path`` down to the first ``steps`` steps of its
    window, as a gzipped XSpace text proto that ``load`` reads: the
    benchmark's host spans of those steps and the ``XLA Ops`` of each chip
    that overlap them, each named by the part of its HLO text that
    ``hlo.parse_event`` reads (as ``tests/data/trim_trace.py`` names them)
    and keeping its ``tf_op``."""
    from google.protobuf import text_format
    space = load(path)
    spans = _spans(space)
    starts = [s for s, _, name in spans if name == trace.SPANS[0]]
    if len(starts) <= steps:
        raise ValueError(f"{path} has {len(starts)} steps, fewer than "
                         f"{steps} + 1")
    lo, hi = starts[0], starts[steps]
    cut = _schema()()
    planes = {}

    def add(where, start, end, name, op_name=None):
        if where not in planes:
            plane = cut.planes.add(id=len(cut.planes) + 1, name=where)
            plane.stat_metadata[1].id, plane.stat_metadata[1].name = 1, TF_OP
            line = plane.lines.add(
                id=1, timestamp_ns=int(lo),
                name="python" if where == "/host:CPU" else trace.OPS_LINE)
            planes[where] = plane, line, {}
        plane, line, ids = planes[where]
        if (name, op_name) not in ids:
            ids[(name, op_name)] = len(ids) + 1
            md = plane.event_metadata[ids[(name, op_name)]]
            md.id, md.name = ids[(name, op_name)], name
            if op_name:
                md.stats.add(metadata_id=1, str_value=op_name)
        line.events.add(metadata_id=ids[(name, op_name)],
                        offset_ps=round((start - lo) * 1e3),
                        duration_ps=round((end - start) * 1e3))

    for start, end, name in spans:
        if lo <= start < hi:
            add("/host:CPU", start, end, name)
    for chip, start, end, text, name in _device_ops(space, chips):
        if start < hi and end > lo:
            add(trace.DEVICE_PLANE + chip, start, end, _short(text), name)
    with gzip.open(out, "wt") as f:
        f.write(text_format.MessageToString(cut))


def _short(text: str) -> str:
    ins = hlo.parse_event(text)
    if not ins.opcode:
        return text
    out = f"%{ins.name} = {ins.result} {ins.opcode}(...)"
    if ins.target:
        out += f', custom_call_target="{ins.target}"'
    if ins.calls:
        out += f", calls=%{ins.calls}"
    return out


def main(argv=None):
    """``python3 -m chipbench.scopes <trace> [--trim OUT]``: print a traced
    run's device time per step by innermost scope and leg, the share of
    busy time under a scope and the largest unscoped ops; or cut the trace
    to one step of test data."""
    import argparse
    import collections
    ap = argparse.ArgumentParser(
        description="A traced run's device time by the program's scopes.")
    ap.add_argument("trace")
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--trim", metavar="OUT")
    args = ap.parse_args(argv)
    if args.trim:
        trim(args.trace, args.trim, chips=args.chips)
        return
    found = ops(args.trace, args.chips)
    steps = sum(1 for _, _, n in _spans(load(args.trace)) if n == "dispatch")
    by_scope = collections.Counter()
    unscoped = collections.Counter()
    for op in found:
        inner = [t for t in re.split(r"[/();:]", op.op_name) if t in NAMES]
        leg = ("bwd" if op.backward else
               "recompute" if "rematted_computation" in op.op_name else "fwd")
        by_scope[(inner[-1] if inner else "-", leg)] += op.seconds
        if not inner:
            unscoped[f"{op.instr.opcode} {op.instr.name} {op.op_name}"] += (
                op.seconds)
    scale = 1e3 / args.chips / steps
    print(f"{steps} steps; {100 * scoped_share(found):.2f}% of busy time "
          f"under a scope; ms per step by (innermost scope, leg):")
    for key, sec in by_scope.most_common():
        print(f"  {key[0]:>10} {key[1]:>9} {sec * scale:9.3f}")
    print("largest unscoped ops, ms per step:")
    for key, sec in unscoped.most_common(10):
        print(f"  {sec * scale:9.3f}  {key}")


if __name__ == "__main__":
    main()
