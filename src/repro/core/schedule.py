"""Plan-driven DSP schedule executor — the ONE place stage-boundary layout
transitions are emitted.

``core.plan`` decides *where* the sharded sequence dimension moves (a shard
dim per stage, minimising paper-Table-2 per-device bytes); this module turns
that plan into the actual transitions, with two interchangeable backends:

* ``backend="explicit"`` — runs *inside* ``shard_map`` on local arrays and
  issues the paper's collective primitives directly: ``dynamic_switch`` (one
  tiled all-to-all, M/N), ``gather`` (one all-gather, M), ``split`` (local
  slice, 0).
* ``backend="auto"``     — runs under ``jit`` on globally-shaped arrays and
  re-constrains the layout (``SeqLayout`` + ``ParallelContext.constrain``);
  XLA SPMD lowers each constraint change to the identical collective
  (asserted by tests/test_hlo_collectives.py).
* ``backend="null"``     — every method is the identity (no mesh / non-DSP
  modes), so model code stays branch-free.

Scanned models (``jax.lax.scan`` over stacked layer params) execute a
*periodic* schedule: the plan over the unrolled stage sequence must repeat
with the layer period (``Schedule.periodic`` validates this) and the scan
body applies the per-period boundary transitions plus the wrap-around
transition back to the period's first layout.  Non-periodic plans execute
through the ``UnrolledSchedule`` view instead: boundaries are addressed by
absolute stage index and the model unrolls its layer loop, so the fwd and
bwd halves of one training step may use different layouts per stage.

The BACKWARD pass is planned too (``core.plan.plan_joint``): a ``Schedule``
may carry ``bwd_dims`` — the cotangent's layout per stage — and the auto
backend executes them through a ``custom_vjp`` on every boundary
constraint: the backward gets its own planned switch sequence instead of
whatever XLA transposes.  Without ``bwd_dims`` the backward is the
autodiff transposition of the forward plan (the mirrored default, which
``plan_joint`` keeps whenever its DP finds no cheaper round trip).  The
explicit shard_map backend only supports the mirrored backward: local
array shapes pin each cotangent to its primal's layout.

Planned backwards compose with ``jax.lax.scan``: a scan-periodic schedule
with distinct ``bwd_dims`` (``Schedule.periodic`` validates the backward
leg's periodicity too) lowers to per-period ``custom_vjp`` boundary
constraints INSIDE the scanned layer loop.  Every cotangent inside the
while body is pinned, but the loop carry between periods is the
compiler's to place: in ``bwd_dims[0]`` (where stage 0's anchor leaves
it) or in ``bwd_dims[period-1]`` (where the wrap takes it), with the same
count per iteration either way.  The *seam* reshard — cotangent creation
at the loss boundary in the ``final`` layout — lands ONCE, outside the
body, followed by the reshard into a ``bwd_dims[0]`` carry; the input
gradient's return to ``initial`` lands once after the loop.
``ScheduleExecutor.expected_bwd_collectives`` accounts exactly this
executed structure for either carry (what the compiled HLO must show),
next to ``Schedule.bwd_transitions`` which prices the unrolled leg.

Models declare ``stages(cfg)`` and consume an executor; they never call
``dynamic_switch`` or issue stage-boundary sharding constraints themselves.
The executor walk-through lives in docs/architecture.md §3.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple, Union

from repro.core.plan import (JointCost, JointPlan, Stage, StrategyPlan,
                             joint_cost_bytes, joint_cost_seconds, make_plan,
                             pair_transition_kinds, plan_cost_bytes,
                             plan_cost_seconds, plan_joint, plan_strategy_dp,
                             plan_switches_2d, plan2d_cost_bytes,
                             plan2d_cost_seconds, strategy_plan_cost,
                             switch_count, transition_kind,
                             _as_pair, _pair_joint)

# HLO collective emitted per transition kind (None = communication-free).
COLLECTIVE_OF = {"switch": "all-to-all", "gather": "all-gather",
                 "split": None, "keep": None}


@dataclasses.dataclass(frozen=True)
class Transition:
    """One stage-boundary layout change (a paper Table-2 primitive)."""

    kind: str                  # "keep" | "switch" | "split" | "gather"
    src: Optional[int]
    tgt: Optional[int]

    @property
    def collective(self) -> Optional[str]:
        return COLLECTIVE_OF[self.kind]


def classify(src: Optional[int], tgt: Optional[int]) -> Transition:
    """Wrap a (src, tgt) layout change as a ``Transition`` (Table-2 kind +
    the HLO collective it must compile to).  docs/architecture.md §1."""
    return Transition(transition_kind(src, tgt), src, tgt)


@dataclasses.dataclass(frozen=True)
class Schedule:
    """A solved plan: shard dim per stage plus entry/exit layouts.

    ``initial`` is the layout the input arrives with (dataloader split);
    ``final`` pins the exit layout (loss/head) or is None for "free".
    ``topology`` is the mesh model the plan was solved against (None = the
    byte-uniform model); it travels with the plan so every consumer — the
    Sharder, the serving engine, benchmarks — prices it consistently.

    ``bwd_dims`` (optional) is the PLANNED backward: the cotangent's shard
    dim while each stage's backward computes, in stage order.  None means
    the mirrored default — the backward retraces the forward plan, which is
    exactly what autodiff transposition executes, so pricing helpers treat
    None as ``dims``.  See docs/architecture.md §2.4/§3.3.

    ``overlap`` ("chunked" | "double_buffer" | None) records the executor
    mode the plan was priced for: switches decompose into per-shard
    ``ppermute`` hops interleaved with the consuming kernel
    (``core.overlap.overlapped_switch``).  ``overlap_mode(t)`` selects the
    mode PER BOUNDARY — only switches whose consuming stage carries a
    ``compute_seconds`` estimate run overlapped; everything else stays
    synchronous.  See docs/architecture.md §3.6.

    ``strategies`` (optional) is the per-stage EXECUTION strategy from the
    unified (stage, dim, strategy) DP (``core.plan.plan_strategy_dp``):
    "dsp" for stages the boundary switches serve (today's behaviour, the
    None default everywhere), or an embedded strategy
    (``core.topology.STRATEGIES``) for stages that compute ON the resident
    shard with in-stage collectives.  ``strategy(t)`` reads it per stage.
    """

    stages: Tuple[Stage, ...]
    dims: Tuple[int, ...]
    initial: Optional[int] = None
    final: Optional[int] = None
    topology: Optional[object] = None
    bwd_dims: Optional[Tuple[int, ...]] = None
    overlap: Optional[str] = None
    strategies: Optional[Tuple[str, ...]] = None

    def __post_init__(self):
        assert len(self.stages) == len(self.dims), (len(self.stages),
                                                    len(self.dims))
        if self.bwd_dims is not None:
            assert len(self.bwd_dims) == len(self.dims), (len(self.bwd_dims),
                                                          len(self.dims))
        if self.strategies is not None:
            assert len(self.strategies) == len(self.dims), (
                len(self.strategies), len(self.dims))
        if self.overlap not in (None, "chunked", "double_buffer"):
            raise ValueError(f"overlap {self.overlap!r}")

    # -- boundary transitions ------------------------------------------------
    def boundary(self, t: int) -> Transition:
        """Transition INTO stage ``t`` (t == 0: from the initial layout)."""
        src = self.initial if t == 0 else self.dims[t - 1]
        return classify(src, self.dims[t])

    def exit(self) -> Transition:
        src = self.dims[-1] if self.dims else self.initial
        return classify(src, self.final if self.final is not None else src)

    def transitions(self) -> List[Transition]:
        out = [self.boundary(t) for t in range(len(self.dims))]
        if self.final is not None:
            out.append(self.exit())
        return out

    # -- per-stage execution strategy ----------------------------------------
    def strategy(self, t: int) -> str:
        """Execution strategy of stage ``t`` ("dsp" when the schedule
        carries no strategy assignment — every pre-strategy plan)."""
        return self.strategies[t] if self.strategies is not None else "dsp"

    @property
    def has_embedded(self) -> bool:
        """True when any stage runs an embedded (non-DSP) strategy."""
        return (self.strategies is not None
                and any(s != "dsp" for s in self.strategies))

    def strategy_seconds(self, topology=None) -> float:
        """Planned seconds of the FULL (dim, strategy) assignment — boundary
        transitions plus each stage's embedded in-stage collectives
        (``core.plan.strategy_plan_cost``; equals ``per_device_seconds``
        for all-"dsp" assignments)."""
        topo = topology if topology is not None else self.topology
        if topo is None:
            raise ValueError("strategy_seconds needs a Topology (none was "
                             "attached at plan time)")
        plan = StrategyPlan(self.dims,
                            self.strategies if self.strategies is not None
                            else ("dsp",) * len(self.dims))
        return strategy_plan_cost(self.stages, plan, n=topo.size,
                                  initial=self.initial, final=self.final,
                                  topology=topo, overlap=self.overlap)

    def expected_strategy_collectives(self, n: int,
                                      outer: int = 1) -> Dict[str, int]:
        """HLO collectives the EMBEDDED stages add per full pass, with the
        conventions of ``analysis.roofline.parse_collectives`` (while-body
        instructions multiply by trip count; K and V rotate as two leaves):
        ulysses/hybrid scatter q,k,v in and o out (4 all-to-alls); a ring
        over a g-device group streams 2g permutes; megatron wraps each
        block in an AG/RS pair.  ``n`` is the full SP degree, ``outer`` the
        hybrid's outer-ring size."""
        counts: Dict[str, int] = {}

        def add(kind: str, k: int):
            if k:
                counts[kind] = counts.get(kind, 0) + k

        for s in (self.strategies or ()):
            if s == "dsp":
                continue
            if s == "ulysses":
                add("all-to-all", 4)
            elif s == "ring":
                add("collective-permute", 2 * n)
            elif s == "megatron":
                add("all-gather", 2)
                add("reduce-scatter", 2)
            elif s == "hybrid":
                add("all-to-all", 4)
                add("collective-permute", 2 * outer)
            else:
                raise ValueError(f"unknown strategy {s!r}")
        return counts

    # -- planned backward ----------------------------------------------------
    @property
    def mirrored(self) -> bool:
        """True when the backward retraces the forward (no separate plan)."""
        return self.bwd_dims is None or self.bwd_dims == self.dims

    @property
    def bwd_plan(self) -> Tuple[int, ...]:
        """Backward layout per stage (the forward dims when mirrored)."""
        return self.bwd_dims if self.bwd_dims is not None else self.dims

    def joint(self) -> JointPlan:
        return JointPlan(self.dims, self.bwd_plan)

    def bwd_seam(self) -> Transition:
        """Cotangent creation at the loss boundary: from the pinned
        ``final`` layout (or the forward's exit layout) into the last
        stage's backward layout."""
        src = self.final if self.final is not None else (
            self.dims[-1] if self.dims else self.initial)
        return classify(src, self.bwd_plan[-1] if self.dims else src)

    def bwd_boundary(self, t: int) -> Transition:
        """Transition of the cotangent leaving stage ``t``'s backward across
        boundary ``t`` (t == 0: the input gradient returns to ``initial``)."""
        bwd = self.bwd_plan
        tgt = self.initial if t == 0 else bwd[t - 1]
        return classify(bwd[t], tgt if tgt is not None else bwd[t])

    def bwd_transitions(self) -> List[Transition]:
        """The backward leg in execution order: seam, then boundaries from
        the last stage back to the input."""
        out = [self.bwd_seam()]
        out.extend(self.bwd_boundary(t)
                   for t in range(len(self.dims) - 1, -1, -1))
        return out

    # -- comm-compute overlap -------------------------------------------------
    def overlap_mode(self, t: int) -> Optional[str]:
        """Executor mode for the boundary INTO stage ``t``: the schedule's
        ``overlap`` mode when that boundary is a switch the consuming stage
        can hide behind (``Stage.compute_seconds`` attached), else None —
        the per-boundary selection the planner priced (gathers don't
        decompose, keeps move nothing, stages without a compute estimate
        have no hide budget)."""
        if self.overlap is None:
            return None
        if self.boundary(t).kind != "switch":
            return None
        if not self.stages[t].compute_seconds:
            return None
        return self.overlap

    def exposed_seconds(self, topology=None) -> float:
        """Planned EXPOSED collective seconds of the forward plan — each
        switch discounted by the consuming stage's ``compute_seconds``
        under this schedule's ``overlap`` mode (``== per_device_seconds``
        when ``overlap`` is None)."""
        topo = topology if topology is not None else self.topology
        if topo is None:
            raise ValueError("exposed_seconds needs a Topology (none was "
                             "attached at plan time)")
        return plan_cost_seconds(self.stages, self.dims, topo,
                                 initial=self.initial, final=self.final,
                                 overlap=self.overlap)

    def hidden_comm_seconds(self, topology=None) -> float:
        """Planned comm seconds the executor HIDES behind kernel compute:
        synchronous cost minus exposed cost (0.0 when ``overlap`` is
        None)."""
        topo = topology if topology is not None else self.topology
        if topo is None:
            raise ValueError("hidden_comm_seconds needs a Topology (none "
                             "was attached at plan time)")
        return self.per_device_seconds(topo) - self.exposed_seconds(topo)

    # -- accounting ----------------------------------------------------------
    def n_switches(self) -> int:
        return sum(1 for tr in self.transitions() if tr.kind == "switch")

    def expected_collectives(self) -> Dict[str, int]:
        """HLO collective kind -> count this schedule must compile to.

        Counts the SYNCHRONOUS lowering; a boundary running overlapped
        (``overlap_mode(t)`` non-None on the explicit backend) lowers its
        all-to-all to ``n - 1`` ``collective-permute`` ops instead —
        tests/test_hlo_collectives.py accounts that form directly."""
        counts: Dict[str, int] = {}
        for tr in self.transitions():
            c = tr.collective
            if c is not None:
                counts[c] = counts.get(c, 0) + 1
        return counts

    def per_device_bytes(self, n: int) -> float:
        """Planned per-device collective bytes (paper Table 2 constant —
        identical to what benchmarks/comm_volume.py prices)."""
        return plan_cost_bytes(self.stages, self.dims, n=n,
                               initial=self.initial, final=self.final)

    def per_device_seconds(self, topology=None) -> float:
        """Planned collective seconds on ``topology`` (defaults to the
        topology the plan was solved against)."""
        topo = topology if topology is not None else self.topology
        if topo is None:
            raise ValueError("per_device_seconds needs a Topology (none was "
                             "attached at plan time)")
        return plan_cost_seconds(self.stages, self.dims, topo,
                                 initial=self.initial, final=self.final)

    def roundtrip_bytes(self, n: int) -> JointCost:
        """Planned per-device bytes of the full training round trip, split
        by leg (``.fwd`` / ``.bwd`` / ``.total``) — what dry-run metas and
        ``benchmarks/comm_volume.py`` report for train cells."""
        return joint_cost_bytes(self.stages, self.joint(), n=n,
                                initial=self.initial, final=self.final)

    def roundtrip_seconds(self, topology=None) -> JointCost:
        """Planned round-trip seconds on ``topology`` (defaults to the one
        the plan was solved against), split by leg."""
        topo = topology if topology is not None else self.topology
        if topo is None:
            raise ValueError("roundtrip_seconds needs a Topology (none was "
                             "attached at plan time)")
        return joint_cost_seconds(self.stages, self.joint(), topo,
                                  initial=self.initial, final=self.final)

    # -- periodic (scan) form ------------------------------------------------
    def periodic(self, period: int) -> "PeriodicSchedule":
        """Validate the plan is steady-state with the given stage period and
        return the scan-body view.  Scanned execution cannot vary layouts
        across iterations, so a non-periodic plan (forward OR planned
        backward) is a hard error — execute those through ``unrolled()``."""
        if len(self.dims) % period:
            raise ValueError(f"{len(self.dims)} stages not a multiple of "
                             f"period {period}")
        for label, dims in (("plan", self.dims),
                            ("backward plan", self.bwd_dims or ())):
            for t, d in enumerate(dims):
                if d != dims[t % period]:
                    raise ValueError(
                        f"{label} is not periodic with period {period}: "
                        f"stage {t} shards dim {d} but stage {t % period} "
                        f"shards {dims[t % period]} (scanned layers need a "
                        f"steady-state plan; pass final=initial, or execute "
                        f"the plan via Schedule.unrolled())")
        for t, s in enumerate(self.strategies or ()):
            if s != self.strategies[t % period]:
                raise ValueError(
                    f"strategy plan is not periodic with period {period}: "
                    f"stage {t} runs {s!r} but stage {t % period} runs "
                    f"{self.strategies[t % period]!r} (scanned layers need "
                    f"a steady-state strategy assignment; execute via "
                    f"Schedule.unrolled())")
        return PeriodicSchedule(self, period)

    def unrolled(self) -> "UnrolledSchedule":
        """Non-periodic (unrolled) execution view: boundaries addressed by
        absolute stage index, no steady-state requirement — the layer loop
        must be python-unrolled instead of scanned."""
        return UnrolledSchedule(self)


@dataclasses.dataclass(frozen=True)
class PeriodicSchedule:
    """Scan-body view of a periodic schedule: entry transition before the
    scan, per-period boundaries inside the body, wrap-around at the body's
    end, exit transition after the scan."""

    schedule: Schedule
    period: int

    @property
    def dims(self) -> Tuple[int, ...]:
        return self.schedule.dims[:self.period]

    @property
    def strategies(self) -> Tuple[str, ...]:
        """Per-period execution strategies (all-"dsp" when the schedule
        carries none); ``Schedule.periodic`` validated periodicity."""
        if self.schedule.strategies is None:
            return ("dsp",) * self.period
        return self.schedule.strategies[:self.period]

    def enter(self) -> Transition:
        return classify(self.schedule.initial, self.dims[0])

    def boundary(self, i: int) -> Transition:
        """Transition into in-period stage ``i`` (1 <= i < period)."""
        assert 1 <= i < self.period, i
        return classify(self.dims[i - 1], self.dims[i])

    def wrap(self) -> Transition:
        """End-of-body transition back to the period's first layout."""
        return classify(self.dims[-1], self.dims[0])

    def exit(self) -> Transition:
        final = self.schedule.final
        return classify(self.dims[0], final if final is not None
                        else self.dims[0])

    # -- planned backward (scan-body view) -----------------------------------
    @property
    def bwd_dims(self) -> Tuple[int, ...]:
        """Per-period backward layouts (the fwd dims when mirrored);
        ``Schedule.periodic`` validated the full backward plan repeats with
        the period, so this prefix IS the steady state."""
        return self.schedule.bwd_plan[:self.period]

    def bwd_seam(self) -> Transition:
        """Cotangent creation at the loss boundary: lands ONCE on the
        backward scan's carry init (outside the while body)."""
        return self.schedule.bwd_seam()

    def bwd_boundary(self, i: int) -> Transition:
        """Cotangent crossing in-period boundary ``i`` backward
        (1 <= i < period): the transpose of ``boundary(i)``'s constraint,
        re-laid-out to the planned backward dims."""
        assert 1 <= i < self.period, i
        bwd = self.bwd_dims
        return classify(bwd[i], bwd[i - 1])

    def bwd_wrap(self) -> Transition:
        """Cotangent leaving the period toward the previous one: the scan
        carry's backward anchor.  The body emits this every iteration, so a
        steady-state plan wants it to be a keep (class-uniform plans with a
        resid-class first and last stage make it one for free)."""
        bwd = self.bwd_dims
        return classify(bwd[0], bwd[-1])

    def bwd_carry_init(self) -> Transition:
        """Reshard of the seam-laid-out cotangent into the backward loop's
        steady-state carry layout (``bwd_dims[0]`` for a stage-0-anchored
        body); lands once, outside the while body, right after the seam."""
        bwd = self.bwd_dims
        return classify(bwd[-1], bwd[0])

    def bwd_enter(self, carry: str = "first") -> Transition:
        """Input gradient leaving the scan for the ``initial`` layout (the
        dataloader split owns both ends); lands once, after the loop.  A
        stage-0-anchored body exits the carry in ``bwd_dims[0]``
        (``carry="first"``); a compiler that holds the carry in
        ``bwd_dims[-1]`` (``"last"``) runs the wrap at the end of each
        iteration and exits from there."""
        initial = self.schedule.initial
        bwd = self.bwd_dims
        src = bwd[0] if carry == "first" else bwd[-1]
        return classify(src, initial if initial is not None else src)


@dataclasses.dataclass(frozen=True)
class UnrolledSchedule:
    """Absolute-index view of a (possibly non-periodic) schedule: entry
    transition, one boundary per stage index, exit transition.  The model's
    layer loop must be python-unrolled — there is no wrap-around, every
    boundary may differ, and the fwd and bwd halves of a training step may
    use different layouts per stage (``Schedule.bwd_dims``)."""

    schedule: Schedule

    @property
    def dims(self) -> Tuple[int, ...]:
        return self.schedule.dims

    @property
    def n_stages(self) -> int:
        return len(self.schedule.dims)

    def enter(self) -> Transition:
        return classify(self.schedule.initial, self.dims[0])

    def boundary(self, t: int) -> Transition:
        """Transition into stage ``t`` (1 <= t < n_stages, absolute)."""
        assert 1 <= t < len(self.dims), t
        return classify(self.dims[t - 1], self.dims[t])

    def exit(self) -> Transition:
        final = self.schedule.final
        return classify(self.dims[-1], final if final is not None
                        else self.dims[-1])


def plan_schedule(stages: Sequence[Stage], seq_dims: Sequence[int], *,
                  n: int = 2, initial: Optional[int] = None,
                  final: Optional[int] = None, topology=None,
                  overlap: Optional[str] = None) -> Schedule:
    """Solve the switching plan (``core.plan.make_plan``: Belady greedy on
    uniform costs, exact DP otherwise — in seconds when a Topology is given)
    and wrap it as a Schedule carrying that topology.

    Args:
      stages: the model's stage declaration (``models.*.stages(cfg)``).
      seq_dims: switchable sequence-dim indices.
      n: SP degree for byte pricing (ignored when ``topology`` is given).
      initial/final: entry layout and pinned exit layout (None = free).
      topology: price plans in seconds on this mesh model.
      overlap: executor overlap mode ("chunked" | "double_buffer"); the
        solver prices each switch at its EXPOSED seconds against the
        consuming stage's ``compute_seconds`` and the mode travels on the
        returned schedule for the executor to pick up.
    Returns:
      a ``Schedule`` with a mirrored (autodiff-transposed) backward.
    """
    dims = make_plan(stages, seq_dims, n=n, initial=initial, final=final,
                     topology=topology, overlap=overlap)
    return Schedule(tuple(stages), tuple(dims), initial=initial, final=final,
                    topology=topology, overlap=overlap)


def plan_joint_schedule(stages: Sequence[Stage], seq_dims: Sequence[int], *,
                        n: int = 2, initial: Optional[int] = None,
                        final: Optional[int] = None, topology=None,
                        couple: bool = False,
                        require_mirrored: bool = False,
                        overlap: Optional[str] = None) -> Schedule:
    """Solve the joint forward+backward round trip
    (``core.plan.plan_joint``) and wrap it as a Schedule.

    The returned schedule carries ``bwd_dims`` ONLY when the joint DP found
    a round trip strictly cheaper than the mirrored plan — so consumers
    (the executor, dry-run metas) get the mirrored default for free on
    symmetric instances.  Same arguments as ``plan_schedule`` plus
    ``couple`` (charge residual re-shards when the backward deviates; leave
    False under full remat) and ``require_mirrored`` (skip the joint DP and
    return the mirrored baseline — for scanned forwards that can only
    execute the autodiff transpose).  See docs/architecture.md §2.4.
    """
    jp = plan_joint(stages, seq_dims, n=n, initial=initial, final=final,
                    topology=topology, couple=couple,
                    require_mirrored=require_mirrored, overlap=overlap)
    return Schedule(tuple(stages), jp.fwd, initial=initial, final=final,
                    topology=topology,
                    bwd_dims=None if jp.mirrored else jp.bwd,
                    overlap=overlap)


def plan_strategy_schedule(stages: Sequence[Stage], seq_dims: Sequence[int],
                           *, n: int = 2, initial: Optional[int] = None,
                           final: Optional[int] = None, topology=None,
                           overlap: Optional[str] = None) -> Schedule:
    """Solve the unified (stage, dim, strategy) DP
    (``core.plan.plan_strategy_dp``) and wrap it as a Schedule that carries
    the per-stage strategy assignment.

    On a uniform (or absent) topology the DP collapses to the classic
    switch planner bit-for-bit and the returned schedule is all-"dsp" —
    byte-identical to ``plan_schedule``'s.  On a tiered fabric
    (e.g. ``Topology.multihost``) stages may come back with embedded
    strategies ("ulysses" / "ring" / "megatron" / "hybrid"); the executor
    and ``Sharder`` read ``Schedule.strategies`` to pick layouts and
    collectives per stage.
    """
    sp = plan_strategy_dp(stages, seq_dims, n=n, initial=initial,
                          final=final, topology=topology, overlap=overlap)
    return Schedule(tuple(stages), sp.dims, initial=initial, final=final,
                    topology=topology, overlap=overlap,
                    strategies=sp.strategies)


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------

def planned_constraint(x, fwd_sharding, bwd_sharding):
    """Sharding constraint with a PLANNED transpose: the forward constrains
    to ``fwd_sharding``; the backward constrains the cotangent to
    ``bwd_sharding`` instead of the autodiff transpose (which would mirror
    the forward layout).  Both ops are mathematically the identity — only
    the SPMD layout, and hence which collectives XLA emits on each pass,
    changes; gradient values are bitwise-tolerably unchanged.

    This is the ONE planned-backward lowering, shared by the
    ``ScheduleExecutor`` boundary path (t2d) and the ``Sharder`` hook path
    (scanned lm/encdec — ``parallel.partition``): emitted inside a scan
    body it becomes the per-period custom_vjp that lets a non-mirrored
    joint plan run under ``jax.lax.scan``."""
    import jax

    @jax.custom_vjp
    def constrain(y):
        return jax.lax.with_sharding_constraint(y, fwd_sharding)

    def fwd_rule(y):
        return jax.lax.with_sharding_constraint(y, fwd_sharding), None

    def bwd_rule(_, g):
        return (jax.lax.with_sharding_constraint(g, bwd_sharding),)

    constrain.defvjp(fwd_rule, bwd_rule)
    return constrain(x)


# executor-internal alias (kept monkeypatchable by tests)
_planned_constraint = planned_constraint


class ScheduleExecutor:
    """Applies a schedule's transitions to activations.

    One executor object serves a whole forward pass; models call
    ``enter`` / ``boundary`` / ``wrap`` / ``exit`` at stage boundaries and
    ``anchor`` to re-assert the current stage layout on intra-stage tensors
    (auto path only — XLA's backward propagation otherwise flips layouts
    mid-stage).  ``psched`` is the execution view of the plan: a
    ``PeriodicSchedule`` (scanned layers, in-period boundary indices) or an
    ``UnrolledSchedule`` (python-unrolled layers, absolute indices, no
    ``wrap``).

    When the schedule carries a planned backward (``Schedule.bwd_dims``)
    and the backend is ``auto``, every boundary constraint is emitted
    through a ``custom_vjp`` whose backward constrains the cotangent to the
    PLANNED backward layout — the backward pass gets its own switch
    sequence instead of the autodiff transposition of the forward's.  The
    explicit backend cannot decouple the two (local array shapes pin each
    cotangent to its primal's layout) and rejects non-mirrored schedules.

    COMM-COMPUTE OVERLAP (explicit backend only): with ``overlap`` set —
    explicitly, or inherited from ``Schedule.overlap`` — every switch whose
    consuming stage carries a ``compute_seconds`` estimate
    (``Schedule.overlap_mode``) is issued as
    ``core.overlap.overlapped_switch``: ``n - 1`` per-shard
    ``ppermute`` hops with no inter-hop dependencies, free for the compiler
    to interleave with the consuming kernel, instead of one blocking
    all-to-all.  The auto backend cannot decompose the all-to-all XLA emits
    for a sharding constraint (overlap there is up to XLA's collective
    pipeliner), so an explicit ``overlap=`` argument with ``backend="auto"``
    is an error while a schedule-carried mode is silently ignored.
    """

    def __init__(self, psched: Optional[Union[PeriodicSchedule,
                                              UnrolledSchedule]], *,
                 backend: str, ctx=None, axis_name: str = "model",
                 batch_dim: int = 0, overlap: Optional[str] = None):
        if backend not in ("explicit", "auto", "null"):
            raise ValueError(backend)
        if backend == "auto" and ctx is None:
            raise ValueError("auto backend needs a ParallelContext")
        if backend != "null" and psched is None:
            raise ValueError(f"{backend} backend needs a schedule")
        if overlap not in (None, "chunked", "double_buffer"):
            raise ValueError(f"overlap {overlap!r}")
        if overlap is not None and backend != "explicit":
            raise ValueError(
                "overlap executes on the explicit backend only: the auto "
                "backend's sharding constraints lower to XLA's own "
                "all-to-all, which this executor cannot decompose")
        self.psched = psched
        self.backend = backend
        self.ctx = ctx
        self.axis_name = axis_name
        self.batch_dim = batch_dim
        self.unrolled = isinstance(psched, UnrolledSchedule)
        sched = psched.schedule if psched is not None else None
        # explicit overlap argument wins; otherwise the explicit backend
        # inherits the mode the planner attached to the schedule
        if overlap is None and backend == "explicit" and sched is not None:
            overlap = sched.overlap
        self.overlap = overlap
        self._planned_bwd = (backend == "auto" and sched is not None
                             and not sched.mirrored)
        if (backend == "explicit" and sched is not None
                and not sched.mirrored):
            raise ValueError(
                "explicit backend executes the mirrored backward only: "
                "shard_map local shapes pin each cotangent to its primal's "
                "layout (use backend='auto' for planned-backward schedules)")

    # -- null factory --------------------------------------------------------
    @classmethod
    def null(cls) -> "ScheduleExecutor":
        return cls(None, backend="null")

    # -- transition application ---------------------------------------------
    def _layout(self, shard_dim: Optional[int], ndim: int):
        from repro.core.layout import SeqLayout
        return SeqLayout(shard_dim=shard_dim, batch_dim=self.batch_dim,
                         ndim=ndim)

    def _constrain(self, x, shard_dim: Optional[int],
                   bwd_dim: Optional[int] = None):
        """Auto-path constraint; with a planned backward active and a
        ``bwd_dim`` given, the cotangent is constrained to the backward
        plan's layout on the way back (custom_vjp) instead of the
        transposed forward layout."""
        layout = self._layout(shard_dim, x.ndim)
        if not self._planned_bwd or bwd_dim is None:
            return self.ctx.constrain(x, layout)
        ctx = self.ctx
        fwd_s = layout.sharding(ctx.mesh, ctx.dp_axes, ctx.sp_axis)
        bwd_s = self._layout(bwd_dim, x.ndim).sharding(
            ctx.mesh, ctx.dp_axes, ctx.sp_axis)
        return _planned_constraint(x, fwd_s, bwd_s)

    def _overlap_for(self, tr: Transition,
                     consumer: Optional[int]) -> Optional[str]:
        """Overlap mode for one applied transition: the executor's mode when
        the transition is a switch whose consuming stage (``consumer``,
        index into ``Schedule.stages``) carries a ``compute_seconds``
        estimate — the same per-boundary selection the planner priced."""
        if self.overlap is None or self.backend != "explicit":
            return None
        if tr.kind != "switch" or consumer is None:
            return None
        if not self.psched.schedule.stages[consumer].compute_seconds:
            return None
        return self.overlap

    def apply(self, x, tr: Transition, bwd_tgt: Optional[int] = None,
              consumer: Optional[int] = None):
        """Apply one boundary transition.  ``bwd_tgt`` is the PLANNED layout
        of the cotangent after it crosses this boundary backward (auto
        backend with a planned-backward schedule only; ignored otherwise).
        ``consumer`` is the stage index whose kernel consumes the
        transitioned tensor — it selects the overlap mode for switches
        (None, e.g. the exit transition, always runs synchronously).  The
        transition's ops, and the collectives XLA inserts for them, carry
        the ``dsp_switch`` scope (``repro.tracing``).  On a mirrored plan
        the transposed constraint keeps the cotangent's layout, so the
        backward's switch lands on the next constraint the cotangent meets,
        a block's anchor, and carries that block's scope."""
        if self.backend == "null":
            return x
        from repro import tracing
        with tracing.scope(tracing.DSP_SWITCH):
            return self._apply(x, tr, bwd_tgt, consumer)

    def _apply(self, x, tr: Transition, bwd_tgt, consumer):
        if self.backend == "auto":
            # re-constrain even on "keep": anchors SPMD propagation at the
            # boundary, lowers to nothing when the layout is unchanged
            return self._constrain(x, tr.tgt, bwd_tgt)
        # explicit: inside shard_map, call the paper's primitive
        from repro.core import dsp
        if tr.kind == "keep":
            return x
        if tr.kind == "switch":
            mode = self._overlap_for(tr, consumer)
            if mode is not None:
                from repro.core.overlap import overlapped_switch
                return overlapped_switch(x, tr.src, tr.tgt, self.axis_name,
                                         mode=mode)
            return dsp.dynamic_switch(x, tr.src, tr.tgt, self.axis_name)
        if tr.kind == "split":
            return dsp.split(x, tr.tgt, self.axis_name)
        if tr.kind == "gather":
            return dsp.gather(x, tr.src, self.axis_name)
        raise ValueError(tr.kind)

    # -- schedule-view conveniences -------------------------------------------
    @property
    def _bwd_plan(self) -> Optional[Tuple[int, ...]]:
        if not self._planned_bwd:
            return None
        return self.psched.schedule.bwd_plan

    def enter(self, x):
        if self.backend == "null":
            return x
        bwdp = self._bwd_plan
        initial = self.psched.schedule.initial if bwdp is not None else None
        # the cotangent leaving ``enter`` is the input gradient: it returns
        # in the dataloader layout
        bwd_tgt = None if bwdp is None else (
            initial if initial is not None else bwdp[0])
        return self.apply(x, self.psched.enter(), bwd_tgt, consumer=0)

    def boundary(self, x, i: int):
        """Transition into stage ``i`` — in-period index for a periodic
        schedule, absolute index for an unrolled one."""
        if self.backend == "null":
            return x
        bwdp = self._bwd_plan
        bwd_tgt = None if bwdp is None else bwdp[i - 1]
        return self.apply(x, self.psched.boundary(i), bwd_tgt, consumer=i)

    def wrap(self, x):
        if self.backend == "null":
            return x
        if self.unrolled:
            raise ValueError("unrolled schedules have no wrap-around; "
                             "iterate boundary(t) over absolute indices")
        bwdp = self._bwd_plan
        bwd_tgt = None if bwdp is None else bwdp[self.psched.period - 1]
        # the wrap feeds the NEXT period's first stage
        return self.apply(x, self.psched.wrap(), bwd_tgt, consumer=0)

    def exit(self, x):
        if self.backend == "null":
            return x
        bwdp = self._bwd_plan
        # the cotangent entering ``exit`` backward is the SEAM: it lands in
        # the last stage's backward layout (periodic bwd plans repeat, so
        # bwdp[-1] == bwdp[period-1] and the subsequent wrap backward is a
        # free "keep" — exactly the one seam transition the cost model
        # prices)
        bwd_tgt = None if bwdp is None else bwdp[-1]
        return self.apply(x, self.psched.exit(), bwd_tgt)

    def anchor(self, x, i: int):
        """Re-assert stage ``i``'s layout on an intra-stage tensor (auto
        path; no-op for explicit — local shapes already encode the layout).
        With a planned backward, the anchor's transpose asserts the stage's
        BACKWARD layout so mid-stage cotangents stay on the planned dim."""
        if self.backend != "auto":
            return x
        bwdp = self._bwd_plan
        return self._constrain(x, self.psched.dims[i],
                               None if bwdp is None else bwdp[i])

    def fold_anchor(self, x):
        """Anchor a stage-folded view (B*other, L, C) whose batch dim has
        absorbed the sharded sequence dim as its MINOR factor (auto path).
        Keeps the composite (dp..., sp) sharding alive across the reshape."""
        if self.backend != "auto":
            return x
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        ctx = self.ctx
        entries: list = [None] * x.ndim
        entries[self.batch_dim] = (*ctx.dp_axes, ctx.sp_axis)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(ctx.mesh, P(*entries)))

    def strategy_for(self, i: int) -> str:
        """Execution strategy of stage ``i`` (in-period index for a periodic
        schedule, absolute for unrolled; "dsp" for strategy-less and null
        schedules) — how the model body should run the stage's collectives:
        DSP boundary switches, or an embedded ulysses / ring / megatron /
        hybrid attention."""
        if self.backend == "null":
            return "dsp"
        sched = self.psched.schedule
        if sched.strategies is None:
            return "dsp"
        return sched.strategies[i if self.unrolled
                                else i % self.psched.period]

    # -- accounting ----------------------------------------------------------
    def expected_collectives(self, n_periods: int = 1) -> Dict[str, int]:
        """Collective counts of the full forward execution — entry + body x
        ``n_periods`` for a periodic schedule (the exit "keep" adds
        nothing), entry + every absolute boundary + exit for an unrolled
        one (``n_periods`` is ignored there)."""
        if self.psched is None:
            return {}
        counts: Dict[str, int] = {}

        def add(tr):
            c = tr.collective
            if c is not None:
                counts[c] = counts.get(c, 0) + 1

        add(self.psched.enter())
        if self.unrolled:
            for t in range(1, self.psched.n_stages):
                add(self.psched.boundary(t))
        else:
            for _ in range(n_periods):
                for i in range(1, self.psched.period):
                    add(self.psched.boundary(i))
                add(self.psched.wrap())
        add(self.psched.exit())
        return counts

    def expected_bwd_collectives(self, n_periods: int = 1,
                                 carry: str = "first") -> Dict[str, int]:
        """Collective counts of the EXECUTED backward leg (auto backend).

        Mirrored schedules transpose the forward constraints, so the leg
        mirrors ``expected_collectives`` (exact for well-formed bodies —
        stage-0 anchored, ``initial == final == dims[0]`` — which every
        scanned model in this repo is).  With a planned backward:

        * periodic (scanned) — the loss cotangent pays the SEAM
          (``final -> bwd[-1]``) ONCE, outside the while body; each body
          iteration emits the reversed in-period boundaries plus the wrap
          transition; the input gradient returns to ``initial`` once, after
          the loop.  ``carry`` says where the loop carry sits, which XLA
          chooses: ``"first"`` (``bwd[0]``) adds the carry-init reshard
          ``bwd[-1] -> bwd[0]`` after the seam and exits from ``bwd[0]``;
          ``"last"`` (``bwd[-1]``) has no carry-init and exits from
          ``bwd[-1]`` — never more collectives than ``"first"``;
        * unrolled — seam + every reversed absolute boundary + the input
          gradient's entry transition (``Schedule.bwd_transitions``).

        tests/test_hlo_collectives.py and tests/test_scan_joint.py compare
        THIS count against the compiled train-step HLO, leg by leg.
        """
        if self.psched is None:
            return {}
        counts: Dict[str, int] = {}

        def add(tr):
            c = tr.collective
            if c is not None:
                counts[c] = counts.get(c, 0) + 1

        sched = self.psched.schedule
        if sched.mirrored:
            # autodiff transposes each forward constraint: same counts
            return self.expected_collectives(n_periods)
        if self.unrolled:
            for tr in sched.bwd_transitions():
                add(tr)
            return counts
        ps = self.psched
        if carry not in ("first", "last"):
            raise ValueError(f"carry {carry!r}")
        add(ps.bwd_seam())                       # final -> bwd[-1], once
        if carry == "first":
            add(ps.bwd_carry_init())             # into the loop carry, once
        for _ in range(n_periods):
            for i in range(ps.period - 1, 0, -1):
                add(ps.bwd_boundary(i))
            add(ps.bwd_wrap())
        add(ps.bwd_enter(carry))                 # input grad -> initial, once
        return counts


# ---------------------------------------------------------------------------
# 2D layouts (TSP fold): schedules over dim pairs on an ("sp_out","sp_in")
# grid — the execution layer of ``core.plan.plan_switches_2d``
# ---------------------------------------------------------------------------

Pair = Tuple[Optional[int], Optional[int]]


@dataclasses.dataclass(frozen=True)
class PairTransition:
    """One stage-boundary 2D layout change.

    Decomposes PER AXIS: component ``k`` classifies with the 1D Table-2
    kinds, and a changed axis owes one SUB-MESH collective over just that
    grid axis — unchanged axes owe nothing.  Diagonal-to-diagonal changes
    (``(d,d) -> (e,e)``, the embedded 1D plans) are JOINT: the executor
    runs them as ONE full-group primitive, exactly the 1D transition."""

    src: Pair
    tgt: Pair

    @property
    def joint(self) -> bool:
        return _pair_joint(self.src, self.tgt)

    @property
    def axis_kinds(self) -> Tuple[str, str]:
        return pair_transition_kinds(self.src, self.tgt)

    @property
    def kind(self) -> str:
        """Coarse kind for display: the joint kind when joint, else
        "keep" if no axis moves data, else "switch"/"gather" if any axis
        does (switch wins — mixed boundaries are dominated by the a2a)."""
        kinds = self.axis_kinds
        if self.joint:
            return kinds[0]
        if "switch" in kinds:
            return "switch"
        if "gather" in kinds:
            return "gather"
        return "keep"

    def collective_counts(self) -> Dict[str, int]:
        """HLO collectives this boundary must compile to: ONE full-group
        primitive for joint changes, one sub-axis collective per changed
        axis otherwise — and NOTHING on unchanged axes (the compiled
        contract pinned by the (2,4) md_scenario)."""
        counts: Dict[str, int] = {}
        kinds = (self.axis_kinds[:1] if self.joint else self.axis_kinds)
        for kind in kinds:
            c = COLLECTIVE_OF[kind]
            if c is not None:
                counts[c] = counts.get(c, 0) + 1
        return counts


def classify2(src, tgt) -> PairTransition:
    """Wrap a 2D layout change as a ``PairTransition`` (ints lift to the
    diagonal, None to fully unsharded)."""
    return PairTransition(_as_pair(src) or (None, None),
                          _as_pair(tgt) or (None, None))


@dataclasses.dataclass(frozen=True)
class Schedule2D:
    """A solved 2D plan: one dim-pair layout per stage plus entry/exit
    layouts, on a ``grid = (n_out, n_in)`` SP mesh.  ``topology`` (axes
    mapped positionally onto the grid) travels with the plan for seconds
    pricing, exactly like the 1D ``Schedule``.  Forward-only: 2D training
    legs are future work (docs/architecture.md §9)."""

    stages: Tuple[Stage, ...]
    layouts: Tuple[Pair, ...]
    grid: Tuple[int, int]
    initial: Optional[Pair] = None
    final: Optional[Pair] = None
    topology: Optional[object] = None

    def __post_init__(self):
        assert len(self.stages) == len(self.layouts), (
            len(self.stages), len(self.layouts))
        object.__setattr__(self, "layouts",
                           tuple(_as_pair(lo) for lo in self.layouts))
        object.__setattr__(self, "initial", _as_pair(self.initial))
        object.__setattr__(self, "final", _as_pair(self.final))

    @property
    def size(self) -> int:
        return self.grid[0] * self.grid[1]

    # -- boundary transitions ------------------------------------------------
    def boundary(self, t: int) -> PairTransition:
        """Transition INTO stage ``t`` (t == 0: from the initial layout)."""
        src = self.initial if t == 0 else self.layouts[t - 1]
        return classify2(src, self.layouts[t])

    def exit(self) -> PairTransition:
        src = self.layouts[-1] if self.layouts else self.initial
        return classify2(src, self.final if self.final is not None else src)

    def transitions(self) -> List[PairTransition]:
        out = [self.boundary(t) for t in range(len(self.layouts))]
        if self.final is not None:
            out.append(self.exit())
        return out

    # -- accounting ----------------------------------------------------------
    def expected_collectives(self) -> Dict[str, int]:
        """HLO collective kind -> count of the unrolled plan (one sub-axis
        collective per changed axis, one full-group primitive per joint
        change, zero on unchanged axes)."""
        counts: Dict[str, int] = {}
        for tr in self.transitions():
            for c, k in tr.collective_counts().items():
                counts[c] = counts.get(c, 0) + k
        return counts

    def per_device_bytes(self) -> float:
        """Planned per-device collective bytes (per-axis Table-2 model —
        ``core.plan.plan2d_cost_bytes``)."""
        return plan2d_cost_bytes(self.stages, self.layouts, grid=self.grid,
                                 initial=self.initial, final=self.final)

    def per_device_seconds(self, topology=None) -> float:
        """Planned collective seconds on ``topology`` (defaults to the one
        the plan was solved against; axes map positionally onto the
        grid)."""
        topo = topology if topology is not None else self.topology
        if topo is None:
            raise ValueError("per_device_seconds needs a Topology (none was "
                             "attached at plan time)")
        return plan2d_cost_seconds(self.stages, self.layouts, topo,
                                   initial=self.initial, final=self.final)

    # -- periodic (scan) form ------------------------------------------------
    def periodic(self, period: int) -> "PeriodicSchedule2D":
        """Validate the plan repeats with ``period`` stages and return the
        scan-body view (same steady-state requirement as the 1D
        ``Schedule.periodic``)."""
        if len(self.layouts) % period:
            raise ValueError(f"{len(self.layouts)} stages not a multiple "
                             f"of period {period}")
        for t, lo in enumerate(self.layouts):
            if lo != self.layouts[t % period]:
                raise ValueError(
                    f"2D plan is not periodic with period {period}: stage "
                    f"{t} holds {lo} but stage {t % period} holds "
                    f"{self.layouts[t % period]}")
        return PeriodicSchedule2D(self, period)


@dataclasses.dataclass(frozen=True)
class PeriodicSchedule2D:
    """Scan-body view of a periodic 2D schedule: entry transition before
    the scan, per-period boundaries inside the body, wrap-around at the
    body's end, exit transition after the scan."""

    schedule: Schedule2D
    period: int

    @property
    def layouts(self) -> Tuple[Pair, ...]:
        return self.schedule.layouts[:self.period]

    def enter(self) -> PairTransition:
        return classify2(self.schedule.initial, self.layouts[0])

    def boundary(self, i: int) -> PairTransition:
        """Transition into in-period stage ``i`` (1 <= i < period)."""
        assert 1 <= i < self.period, i
        return classify2(self.layouts[i - 1], self.layouts[i])

    def wrap(self) -> PairTransition:
        """End-of-body transition back to the period's first layout."""
        return classify2(self.layouts[-1], self.layouts[0])

    def exit(self) -> PairTransition:
        final = self.schedule.final
        return classify2(self.layouts[0], final if final is not None
                         else self.layouts[0])


def plan2d_schedule(stages: Sequence[Stage], seq_dims: Sequence[int], *,
                    grid: Tuple[int, int], initial=None, final=None,
                    topology=None) -> Schedule2D:
    """Solve the 2D switching plan (``core.plan.plan_switches_2d`` — exact
    DP over (stage, dim pair), delegating to the 1D DP on degenerate grids)
    and wrap it as a ``Schedule2D`` carrying the grid and topology."""
    layouts = plan_switches_2d(stages, seq_dims, grid=grid, initial=initial,
                               final=final, topology=topology)
    return Schedule2D(tuple(stages), tuple(layouts), grid=tuple(grid),
                      initial=initial, final=final, topology=topology)


class ScheduleExecutor2D:
    """Applies a 2D schedule's transitions to activations (auto backend:
    per-axis ``NamedSharding`` constraints on a 2-axis SP mesh; XLA SPMD
    lowers each single-axis layout change to ONE sub-axis all-to-all and
    emits nothing on unchanged axes — the compiled contract of the (2,4)
    md_scenario).  ``backend="null"`` is the identity, so model code stays
    branch-free.  Forward-only (no planned backward): the 2D training leg
    is future work."""

    def __init__(self, psched: Optional[PeriodicSchedule2D], *,
                 backend: str, mesh=None,
                 sp_axes: Tuple[str, str] = ("sp_out", "sp_in"),
                 dp_axes: Tuple[str, ...] = (), batch_dim: int = 0):
        if backend not in ("auto", "null"):
            raise ValueError(backend)
        if backend == "auto" and mesh is None:
            raise ValueError("auto backend needs a mesh")
        if backend != "null" and psched is None:
            raise ValueError(f"{backend} backend needs a schedule")
        self.psched = psched
        self.backend = backend
        self.mesh = mesh
        self.sp_axes = tuple(sp_axes)
        self.dp_axes = tuple(dp_axes)
        self.batch_dim = batch_dim
        # per-stage diagonal component order (major axis first) — see
        # _stage_order; fixed per stage so boundaries and anchors agree
        self._orders = (tuple(self._stage_order(i)
                              for i in range(psched.period))
                        if psched is not None else ())

    @classmethod
    def null(cls) -> "ScheduleExecutor2D":
        return cls(None, backend="null")

    def _stage_order(self, i: int) -> Tuple[int, int]:
        """Component order for stage ``i``'s DIAGONAL layout: which grid
        axis is MAJOR in the joint (axis, axis) sharding of the dim.

        For a single-axis transition into a diagonal the UNCHANGED axis —
        the one already sharding the dim — must stay major: the target
        shard of every device is then contained in its source shard along
        the kept axis, so the reshard moves data only within sub-groups of
        the CHANGED axis (one sub-axis all-to-all; any other order forces
        cross-group traffic on the axis that nominally "kept" its layout).
        Derived from the in-period predecessor (the steady-state wrap view),
        defaulting to grid order (outer major) — which is also the joint
        diagonal-to-diagonal convention the embedded 1D plans use."""
        lo = self.psched.layouts[i]
        if lo is None or lo[0] is None or lo[0] != lo[1]:
            return (0, 1)
        prev = self.psched.layouts[i - 1] if i > 0 else self.psched.layouts[-1]
        prev = prev or (None, None)
        keep = [k for k in (0, 1) if prev[k] == lo[k]]
        if len(keep) == 1:
            return (keep[0], 1 - keep[0])
        return (0, 1)

    # -- constraint emission --------------------------------------------------
    def _sharding(self, layout: Pair, ndim: int, *,
                  order: Tuple[int, int] = (0, 1), dims=None, batch_dim=None):
        """NamedSharding for a 2D layout on an ``ndim`` tensor.  ``dims``
        maps stage-view dims to tensor dims (identity by default) — the
        model passes it for stacked/folded tensors whose axes are permuted
        or merged relative to the logical stage view; a component landing
        on an already-sharded dim (e.g. a sequence dim folded into the dp
        batch) appends as the MINOR factor.  ``order`` sequences the pair's
        components major-first (see ``_stage_order``)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        entries: list = [None] * ndim
        bd = self.batch_dim if batch_dim is None else batch_dim
        if self.dp_axes and bd is not None:
            entries[bd] = self.dp_axes
        pair = layout or (None, None)
        for k in order:
            d = pair[k]
            if d is None:
                continue
            axis = self.sp_axes[k]
            td = dims[d] if dims is not None else d
            cur = entries[td]
            if cur is None:
                entries[td] = axis
            elif isinstance(cur, tuple):
                if axis not in cur:
                    entries[td] = cur + (axis,)
            elif cur != axis:
                entries[td] = (cur, axis)
        return NamedSharding(self.mesh, P(*entries))

    def constrain(self, x, layout: Pair, *, order: Tuple[int, int] = (0, 1),
                  dims=None, batch_dim=None):
        """Constrain ``x`` to a 2D layout (component k of the pair shards
        tensor dim ``layout[k]`` over ``sp_axes[k]``; the diagonal shards
        one dim jointly in ``order``)."""
        if self.backend == "null":
            return x
        import jax
        return jax.lax.with_sharding_constraint(
            x, self._sharding(_as_pair(layout), x.ndim, order=order,
                              dims=dims, batch_dim=batch_dim))

    def apply(self, x, tr: PairTransition, **kw):
        if self.backend == "null":
            return x
        from repro import tracing
        with tracing.scope(tracing.DSP_SWITCH):
            return self.constrain(x, tr.tgt, **kw)

    # -- schedule-view conveniences -------------------------------------------
    def enter(self, x, **kw):
        if self.backend == "null":
            return x
        return self.apply(x, self.psched.enter(), order=self._orders[0], **kw)

    def boundary(self, x, i: int, **kw):
        if self.backend == "null":
            return x
        return self.apply(x, self.psched.boundary(i), order=self._orders[i],
                          **kw)

    def wrap(self, x, **kw):
        if self.backend == "null":
            return x
        return self.apply(x, self.psched.wrap(), order=self._orders[0], **kw)

    def exit(self, x, **kw):
        if self.backend == "null":
            return x
        return self.apply(x, self.psched.exit(), **kw)

    def anchor(self, x, i: int, **kw):
        """Re-assert in-period stage ``i``'s layout on an intra-stage
        tensor (XLA's backward propagation otherwise flips layouts
        mid-stage)."""
        if self.backend == "null":
            return x
        return self.constrain(x, self.psched.layouts[i],
                              order=self._orders[i], **kw)

    def fold_anchor(self, x, i: int, *, dims, merge_dim: int = 0):
        """Anchor a stage-folded view whose dim ``merge_dim`` absorbed a
        sharded sequence dim as its MAJOR factor (batch minor — the only
        merge order GSPMD can represent for a sharded factor; the dp axes
        append as the minor entries).  ``dims`` maps stage-view dims to the
        folded tensor's dims as in ``constrain``."""
        if self.backend == "null":
            return x
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        ns = self._sharding(self.psched.layouts[i], x.ndim,
                            order=self._orders[i], dims=dims, batch_dim=None)
        entries = list(ns.spec) + [None] * (x.ndim - len(ns.spec))
        if self.dp_axes:
            cur = entries[merge_dim]
            cur = (cur if isinstance(cur, tuple)
                   else () if cur is None else (cur,))
            entries[merge_dim] = cur + tuple(self.dp_axes)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*entries)))

    # -- accounting ----------------------------------------------------------
    def expected_collectives(self, n_periods: int = 1) -> Dict[str, int]:
        """Collective counts of the full forward execution: entry + body x
        ``n_periods`` + exit, each boundary contributing one sub-axis
        collective per changed axis (one full-group primitive when
        joint)."""
        if self.psched is None:
            return {}
        counts: Dict[str, int] = {}

        def add(tr: PairTransition):
            for c, k in tr.collective_counts().items():
                counts[c] = counts.get(c, 0) + k

        add(self.psched.enter())
        for _ in range(n_periods):
            for i in range(1, self.psched.period):
                add(self.psched.boundary(i))
            add(self.psched.wrap())
        add(self.psched.exit())
        return counts

    def expected_carry_collectives(self, n_periods: int = 1) -> Dict[str, int]:
        """Collective counts when the scan CARRY holds the LAST in-period
        stage's layout and the transition into stage 0 executes inside the
        body (``models.transformer2d.forward2d``: the attention-core
        layouts live strictly inside the block, so the first in-period
        boundary lands on the stacked qkv as the wrap): entry
        initial -> layouts[-1], then per period wrap + boundaries 1..p-1,
        then exit layouts[-1] -> final."""
        if self.psched is None:
            return {}
        counts: Dict[str, int] = {}

        def add(tr: PairTransition):
            for c, k in tr.collective_counts().items():
                counts[c] = counts.get(c, 0) + k

        sched = self.psched.schedule
        add(classify2(sched.initial, self.psched.layouts[-1]))
        for _ in range(n_periods):
            add(self.psched.wrap())
            for i in range(1, self.psched.period):
                add(self.psched.boundary(i))
        final = sched.final
        if final is not None:
            add(classify2(self.psched.layouts[-1], final))
        return counts


__all__ = [
    "Transition", "classify", "Schedule", "PeriodicSchedule",
    "UnrolledSchedule", "plan_schedule", "plan_joint_schedule",
    "plan_strategy_schedule", "ScheduleExecutor", "planned_constraint",
    "COLLECTIVE_OF",
    "PairTransition", "classify2", "Schedule2D", "PeriodicSchedule2D",
    "plan2d_schedule", "ScheduleExecutor2D",
]
