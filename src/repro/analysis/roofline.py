"""Roofline analysis from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), in seconds (TPU v5e constants):

  compute    = HLO_FLOPs / (chips * 197e12 bf16 FLOP/s)
  memory     = HLO_bytes / (chips * 819e9 B/s HBM)
  collective = priced on a ``core.topology.Topology`` (per-link alpha+beta
               model; defaults to the flat-ICI line rate, bytes / 50e9 B/s —
               the ICI_BW constant now lives in ``core.topology`` and is
               re-exported here)

Two XLA accounting gotchas handled here:

1. ``compiled.cost_analysis()`` counts a ``while`` (lax.scan) body ONCE —
   verified empirically.  Layer stacks are scanned, so raw numbers would
   undercount by ~n_periods.  FLOPs/bytes therefore use *depth
   extrapolation*: compile the same arch at depth 1 period and 2 periods;
   per-period cost = F(2) - F(1); total = F(1) + (T-1) * (F(2) - F(1)).
   (Cost is affine in depth — layers are homogeneous per period.)

2. collective_bytes is not in cost_analysis at all: we parse the compiled
   HLO text, sum the result-shape bytes of every all-gather / all-reduce /
   reduce-scatter / all-to-all / collective-permute instruction, and
   multiply instructions inside while bodies by the loop trip count
   (recovered from the loop condition's comparison constant).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

from repro.core.topology import ICI_BW, Topology  # single source of truth

# TPU v5e, per chip (compute/memory ceilings; link constants live in
# core.topology)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s

COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}


def _shape_bytes(ty: str) -> int:
    """'bf16[2,8,4]{3,2,1}' -> byte size.  Tuples handled by caller."""
    m = re.match(r"([a-z0-9]+)\[([0-9,]*)\]", ty)
    if not m:
        return 0
    dt, dims = m.group(1), m.group(2)
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def _instruction_result_bytes(line: str) -> int:
    """Sum byte sizes of the result type(s) on an HLO instruction line."""
    rhs = line.split("=", 1)[1].strip()
    if rhs.startswith("("):                      # tuple result (per-peer arrays
        m = re.match(r"\((.*?)\)\s+[a-z0-9-]+\(", rhs)   # or async -start)
        inner = m.group(1) if m else rhs[1:]
        return sum(_shape_bytes(t)
                   for t in re.findall(r"[a-z0-9]+\[[0-9,]*\]", inner))
    return _shape_bytes(rhs)


@dataclasses.dataclass
class CollectiveStats:
    bytes_per_device: float
    count: float
    by_kind: Dict[str, float]
    by_kind_count: Dict[str, float]


def _split_computations(hlo: str) -> Dict[str, List[str]]:
    comps: Dict[str, List[str]] = {}
    cur = None
    for line in hlo.splitlines():
        s = line.strip()
        # computation headers look like: [ENTRY] %name (params...) -> type {
        # params may nest tuple parens, so match only the name prefix
        m = (re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)\s*\(", s)
             if (s.endswith("{") and "->" in s) else None)
        if m:
            cur = m.group(1)
            comps[cur] = []
            continue
        if s == "}":
            cur = None
            continue
        if cur is not None:
            comps[cur].append(s)
    return comps


def _trip_count(cond_lines: List[str]) -> int:
    """Recover lax.scan trip count from the while condition: the comparison
    constant (direction=LT) is the bound.  The TPU compiler prints a layout
    after the scalar type (``s32[]{:T(128)}``)."""
    consts = {}
    for ln in cond_lines:
        m = re.match(r"%?([\w.\-]+)\s*=\s*s32\[\](?:\{[^}]*\})?\s*"
                     r"constant\((\d+)\)", ln)
        if m:
            consts[m.group(1)] = int(m.group(2))
    for ln in cond_lines:
        if "compare(" in ln and "direction=LT" in ln:
            for name, val in consts.items():
                if re.search(rf"%?{re.escape(name)}\b", ln.split("compare", 1)[1]):
                    return val
    # fallback: single constant in the condition
    if len(consts) == 1:
        return next(iter(consts.values()))
    return 1


def _while_map(comps: Dict[str, List[str]]) -> Dict[str, int]:
    """computation name -> multiplier (product of enclosing trip counts)."""
    # map body -> trip count
    body_trip: Dict[str, int] = {}
    parents: Dict[str, List[str]] = {}
    for cname, lines in comps.items():
        for ln in lines:
            m = re.search(r"while\(.*?\)\s*,\s*condition=%?([\w.\-]+)\s*,"
                          r"\s*body=%?([\w.\-]+)", ln)
            if m:
                cond, body = m.group(1), m.group(2)
                body_trip[body] = _trip_count(comps.get(cond, []))
                parents.setdefault(body, []).append(cname)
        # nested calls (fusions/regions) inherit the caller's multiplier
        for ln in lines:
            for m in re.finditer(r"(?:calls=|to_apply=|body=|condition=)"
                                 r"%?([\w.\-]+)", ln):
                parents.setdefault(m.group(1), []).append(cname)

    mult: Dict[str, int] = {}

    def resolve(name: str, seen=()) -> int:
        if name in mult:
            return mult[name]
        if name in seen:
            return 1
        m = body_trip.get(name, 1)
        ps = parents.get(name, [])
        pm = max((resolve(p, seen + (name,)) for p in ps), default=1)
        mult[name] = m * pm
        return mult[name]

    for name in comps:
        resolve(name)
    return mult


def _group_size(line: str) -> int:
    """Participant count of a collective from its replica_groups attr."""
    m = re.search(r"replica_groups=\{\{([0-9,]+)\}", line)
    if m:
        return m.group(1).count(",") + 1
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:
        return int(m.group(2))
    return 1


def parse_collectives(hlo: str) -> CollectiveStats:
    """Per-device logical volume, paper Table 2/3 conventions:
      all-to-all           result bytes          (M/N moves per device)
      all-gather           result bytes          (device receives M)
      reduce-scatter       result bytes x group  (device sends M)
      all-reduce           2 x result bytes      (ring RS+AG)
      collective-permute   result bytes
    Instructions inside while bodies multiply by the loop trip count."""
    comps = _split_computations(hlo)
    mult = _while_map(comps)
    by_kind: Dict[str, float] = {k: 0.0 for k in COLLECTIVES}
    by_count: Dict[str, float] = {k: 0.0 for k in COLLECTIVES}
    for cname, lines in comps.items():
        m = mult.get(cname, 1)
        for ln in lines:
            if "=" not in ln:
                continue
            for kind in COLLECTIVES:
                # match '<kind>(' or '<kind>-start(' as the instruction op
                if re.search(rf"\s{kind}(?:-start)?\(", ln):
                    nbytes = _instruction_result_bytes(ln)
                    if kind == "reduce-scatter":
                        nbytes *= _group_size(ln)
                    elif kind == "all-reduce":
                        nbytes *= 2
                    by_kind[kind] += nbytes * m
                    by_count[kind] += m
                    break
    total = sum(by_kind.values())
    count = sum(by_count.values())
    return CollectiveStats(total, count,
                           {k: v for k, v in by_kind.items() if v},
                           {k: v for k, v in by_count.items() if v})


def op_scope(line: str) -> str:
    """The innermost stage scope (``repro.tracing.SCOPES``) in the
    ``op_name`` of one HLO instruction's text, or '' where it has none."""
    from repro.tracing import SCOPES
    m = re.search(r'op_name="([^"]*)"', line)
    found = [t for t in re.split(r"[/();]", m.group(1))
             if t in SCOPES] if m else []
    return found[-1] if found else ""


def parse_data_collectives(hlo: str, where=None) -> CollectiveStats:
    """``parse_collectives`` minus XLA partitioner artifacts: collectives
    whose every operand is a broadcast of a SCALAR CONSTANT.  When stage
    layouts alternate, the partitioner hoists constant broadcasts (norm eps,
    mean divisors) out of loop bodies and re-tiles them with real
    collectives that move zero information.  The HLO contract tests
    (tests/test_hlo_collectives.py) compare THIS count against the planned
    schedule — one all-to-all per planned switch, on activations.
    ``where``, given, keeps only the instructions whose text it accepts
    (e.g. ``lambda ln: op_scope(ln) == "dsp_switch"``)."""
    comps = _split_computations(hlo)
    mult = _while_map(comps)
    defs: Dict[str, str] = {}
    for lines in comps.values():
        for ln in lines:
            m = re.match(r"%?([\w.\-]+)\s*=", ln)
            if m:
                defs[m.group(1)] = ln

    def scalar_const_broadcast(name: str) -> bool:
        d = defs.get(name, "")
        return bool(re.search(r"=\s*\S+\s+broadcast\(\w+\[\]", d))

    def artifact(ln: str, kind: str) -> bool:
        args = ln.split(f"{kind}(", 1)[-1] if f"{kind}(" in ln else \
            ln.split(f"{kind}-start(", 1)[-1]
        # operand list precedes the first attribute (replica_groups/...)
        args = args.split("), ")[0] if "), " in args else args
        ops = re.findall(r"%([\w.\-]+)", args)
        return bool(ops) and all(scalar_const_broadcast(o) for o in ops)

    by_kind: Dict[str, float] = {k: 0.0 for k in COLLECTIVES}
    by_count: Dict[str, float] = {k: 0.0 for k in COLLECTIVES}
    for cname, lines in comps.items():
        m = mult.get(cname, 1)
        for ln in lines:
            if "=" not in ln or (where is not None and not where(ln)):
                continue
            for kind in COLLECTIVES:
                if re.search(rf"\s{kind}(?:-start)?\(", ln):
                    if not artifact(ln, kind):
                        nbytes = _instruction_result_bytes(ln)
                        if kind == "reduce-scatter":
                            nbytes *= _group_size(ln)
                        elif kind == "all-reduce":
                            nbytes *= 2
                        by_kind[kind] += nbytes * m
                        by_count[kind] += m
                    break
    return CollectiveStats(sum(by_kind.values()), sum(by_count.values()),
                           {k: v for k, v in by_kind.items() if v},
                           {k: v for k, v in by_count.items() if v})


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    hlo_flops: float
    hlo_bytes: float
    collective_bytes_per_dev: float
    model_flops: float
    useful_ratio: float
    bottleneck: str

    def as_dict(self):
        return dataclasses.asdict(self)


def roofline(*, hlo_flops_per_dev: float, hlo_bytes_per_dev: float,
             collective_bytes_per_dev: float, chips: int,
             model_flops: float,
             topology: Optional[Topology] = None) -> Roofline:
    """``topology`` prices the collective term on the modeled fabric
    (bottleneck link of an ICI x DCN mesh, etc.); default is the flat-ICI
    line rate — bytes / ICI_BW, the historical behaviour."""
    compute_s = hlo_flops_per_dev / PEAK_FLOPS
    memory_s = hlo_bytes_per_dev / HBM_BW
    if topology is None:
        collective_s = collective_bytes_per_dev / ICI_BW
    else:
        collective_s = topology.seconds_for_bytes(collective_bytes_per_dev)
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bott = max(terms, key=terms.get)
    total_hlo_flops = hlo_flops_per_dev * chips
    return Roofline(compute_s, memory_s, collective_s,
                    total_hlo_flops, hlo_bytes_per_dev * chips,
                    collective_bytes_per_dev,
                    model_flops,
                    model_flops / total_hlo_flops if total_hlo_flops else 0.0,
                    bott)


def extrapolate_depth(f1: float, f2: float, periods: int) -> float:
    """Affine-in-depth extrapolation: cost(T) = f1 + (T-1)*(f2-f1)."""
    return f1 + (periods - 1) * (f2 - f1)


# ---------------------------------------------------------------------------
# Per-stage compute estimate (the overlap planner's hide budget)
# ---------------------------------------------------------------------------

def stage_flops(stage, cfg) -> float:
    """Dense-kernel FLOPs of one planner stage (GLOBAL, all devices).

    Derived from the stage's declared activation shape — ``(..., L_i ...,
    d_model)``, sequence extents in the middle — and the model config's
    widths, with the standard 2-FLOPs-per-MAC convention the roofline
    report already uses:

    * a mixer stage (``compute_dims`` non-empty): qkvo projections
      ``8·T·d²`` plus attention score+value matmuls ``4·T·L·d`` with ``L``
      the product of the compute-dim extents (the flash-attention kernel's
      inner length);
    * a channel stage (``compute_dims`` empty... or rather no sequence dim
      forbidden beyond the projections): the FFN matmuls ``k·T·d·d_ff``
      with ``k = 4`` (up+down) or ``6`` for gated MLPs.

    ``T`` is the token count ``prod(shape[:-1])``.  Returns 0.0 when the
    stage carries no shape or the config lacks ``d_model`` — the planner
    then treats the boundary as fully exposed, reproducing the synchronous
    plan.
    """
    if stage.shape is None:
        return 0.0
    d = getattr(cfg, "d_model", None)
    if not d:
        return 0.0
    tokens = 1
    for e in stage.shape[:-1]:
        tokens *= e
    if stage.compute_dims:
        length = 1
        for dim in stage.compute_dims:
            if dim < len(stage.shape):
                length *= stage.shape[dim]
        return 8.0 * tokens * d * d + 4.0 * tokens * length * d
    d_ff = getattr(cfg, "d_ff", None) or 4 * d
    gated = "glu" in str(getattr(cfg, "mlp_kind", "")).lower()
    return (6.0 if gated else 4.0) * tokens * d * d_ff


def stage_compute_seconds(stage, cfg, topology=None) -> float:
    """Per-device kernel seconds of one planner stage — the compute budget
    an overlapped switch into it can hide behind (``Topology
    .exposed_seconds``; the ``overlap=`` arguments of ``core.plan``).

    One convention with the roofline report: seconds are
    ``flops_per_device / PEAK_FLOPS``, exactly ``roofline(...).compute_s``
    for the same per-device FLOPs.  The stage's tokens divide evenly over
    the SP group (DSP computes on full sequences with the OTHER dim
    sharded), so per-device FLOPs are ``stage_flops / topology.size``
    (``topology=None`` or an int degree are accepted).
    """
    f = stage_flops(stage, cfg)
    if not f:
        return 0.0
    if topology is None:
        n = 1
    elif isinstance(topology, int):
        n = max(topology, 1)
    else:
        n = topology.size
    return f / n / PEAK_FLOPS


def attach_compute_seconds(stages, cfg, topology=None):
    """Return the stage list with ``Stage.compute_seconds`` filled from
    ``stage_compute_seconds`` (stages that already declare one keep it) —
    what ``models.*.dsp_schedule(overlap=...)`` feeds the overlap-aware
    planner."""
    import dataclasses as _dc
    return [st if st.compute_seconds is not None else
            _dc.replace(st, compute_seconds=stage_compute_seconds(
                st, cfg, topology))
            for st in stages]
