"""First-class 2D layouts (TSP fold): plan over dim pairs, price per axis.

The load-bearing properties of the (stage, layout) generalization:

  * COLLAPSE — on a degenerate ``(n, 1)`` / ``(1, n)`` grid the 2D planner
    reproduces the 1D DP's plan (lifted to the diagonal) and its cost
    BIT-FOR-BIT, so the whole 2D machinery is a conservative extension.
  * PER-AXIS PRICING — a transition changing exactly one grid axis costs
    exactly the 1D Table-2 primitive of that component on the sub-mesh
    fiber; unchanged axes cost zero; diagonal-to-diagonal (joint) changes
    cost ONE full-group primitive (what the executor runs).
  * EXACTNESS — the 2D DP matches the exponential brute-force oracle.

Each property runs twice: an exhaustive deterministic sweep over a small
instance space (always on), and a wider randomized search when hypothesis
is installed.  Multi-device execution of these plans (sharded bit-parity +
the one-sub-axis-a2a-per-changed-axis HLO pin) lives in
tests/md_scenarios.py::scenario_layout2d_t2d.
"""
import itertools

import pytest

from repro.core.dsp import comm_volume_bytes
from repro.core.plan import (Stage, brute_force_plan2d, layout_allows,
                             pair_placement_equal, pair_transition_bytes,
                             pair_transition_kinds, plan_cost_bytes,
                             plan_switches_dp, plan_switches_2d,
                             plan2d_cost_bytes)

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False

DIMS = [1, 2, 3]


def _assert_collapse(stages, dims, grid, initial, final):
    """(n,1)/(1,n) grids: same plan (lifted to the diagonal), same cost —
    exact float equality, not approx: both sides must walk the identical
    comm_volume_bytes arithmetic."""
    n = grid[0] * grid[1]
    plan1 = plan_switches_dp(stages, dims, n=n, initial=initial, final=final)
    plan2 = plan_switches_2d(stages, dims, grid=grid, initial=initial,
                             final=final)
    assert plan2 == [(d, d) for d in plan1]
    cost1 = plan_cost_bytes(stages, plan1, n=n, initial=initial, final=final)
    cost2 = plan2d_cost_bytes(stages, plan2, grid=grid, initial=initial,
                              final=final)
    assert cost2 == cost1
    # the lifted plan places data identically to the 1D plan on this grid
    assert all(pair_placement_equal(lo, d, grid)
               for lo, d in zip(plan2, plan1))


def _assert_dp_exact(stages, dims, grid, initial, final):
    plan = plan_switches_2d(stages, dims, grid=grid, initial=initial,
                            final=final)
    for st_, lo in zip(stages, plan):
        assert layout_allows(st_, lo, grid)
    cost = plan2d_cost_bytes(stages, plan, grid=grid, initial=initial,
                             final=final)
    best = brute_force_plan2d(stages, dims, grid=grid, initial=initial,
                              final=final)
    assert cost == best


def _sweep_instances(dims, max_stages, shape):
    """Every forbid-set pattern (each stage leaves >=1 dim free) x every
    initial/final pinning, on one byte-asymmetric shape."""
    forbids = [frozenset(f) for r in range(len(dims))
               for f in itertools.combinations(dims, r)]
    ends = [None] + list(dims)
    for n_stages in range(1, max_stages + 1):
        for pattern in itertools.product(forbids, repeat=n_stages):
            stages = [Stage(f, f"s{i}", shape)
                      for i, f in enumerate(pattern)]
            for initial, final in itertools.product(ends, ends):
                yield stages, initial, final


# ---------------------------------------------------------------------------
# Collapse: degenerate grids reproduce the 1D DP bit-for-bit
# ---------------------------------------------------------------------------

def test_degenerate_grid_collapse_exhaustive():
    dims = [1, 2]
    shape = (2, 64, 8, 512)
    for stages, initial, final in _sweep_instances(dims, 3, shape):
        for grid in ((4, 1), (1, 4), (2, 1), (1, 2)):
            _assert_collapse(stages, dims, grid, initial, final)


def test_1x1_grid_plan_is_periodic_and_stable():
    """Size-1 fabric: greedy keep-else-smallest — a periodic stage sequence
    yields a periodic plan (the unrolled DP's equal-cost tie-breaks don't:
    at n=1 switches still price M, so it minimizes switch COUNT and may
    break the tail)."""
    period = [Stage(frozenset({2}), "attn"), Stage(frozenset({3}), "mlp")]
    plan = plan_switches_2d(period * 4, [1, 2, 3], grid=(1, 1),
                            initial=(1, 1))
    assert plan == [(1, 1), (1, 1)] * 4
    # a stage forbidding the carried dim forces the smallest allowed dim —
    # still periodic when the stage sequence is
    forced = [Stage(frozenset({2}), "attn"), Stage(frozenset({1}), "mlp")]
    plan = plan_switches_2d(forced * 4, [1, 2], grid=(1, 1), initial=(1, 1))
    assert plan == [(1, 1), (2, 2)] * 4


# ---------------------------------------------------------------------------
# Per-axis transition pricing ties back to Table 2
# ---------------------------------------------------------------------------

def test_single_axis_change_prices_as_sub_mesh_table2():
    """Exactly one changed axis => exactly the 1D Table-2 bytes of that
    component's change, on the fiber the other axis leaves visible
    (M / other_grid_size), over the changed axis' sub-mesh."""
    M = 4096.0
    for a, b, c in itertools.product(DIMS, repeat=3):
        if b == c:
            continue  # no change anywhere
        for grid in ((2, 4), (4, 2), (2, 2), (8, 3)):
            for k in (0, 1):  # the changed axis
                src = (b, a) if k == 0 else (a, b)
                tgt = (c, a) if k == 0 else (a, c)
                fiber = M / grid[1 - k]
                expected = comm_volume_bytes("switch", fiber, grid[k])
                assert pair_transition_bytes(src, tgt, M, grid) == expected
                kinds = pair_transition_kinds(src, tgt)
                assert kinds[k] == "switch" and kinds[1 - k] == "keep"


def test_joint_diagonal_change_prices_as_full_group():
    """Diagonal-to-diagonal = the embedded 1D plan's transition: ONE
    full-group primitive over n = grid[0]*grid[1] — the equality that makes
    the collapse property's costs bit-identical."""
    M = 4096.0
    for d, e in itertools.product(DIMS, repeat=2):
        for grid in ((2, 4), (4, 2), (3, 5)):
            n = grid[0] * grid[1]
            kind = "keep" if d == e else "switch"
            assert (pair_transition_bytes((d, d), (e, e), M, grid)
                    == comm_volume_bytes(kind, M, n))


def test_both_axes_change_sums_per_axis_collectives():
    # (1,2) -> (2,3): outer re-tiles its M/4 fiber over 2 devices, inner its
    # M/2 fiber over 4 — two sub-mesh all-to-alls, summed
    M = 4096.0
    got = pair_transition_bytes((1, 2), (2, 3), M, (2, 4))
    assert got == (M / 4) / 2 + (M / 2) / 4
    assert pair_transition_kinds((1, 2), (2, 3)) == ("switch", "switch")


# ---------------------------------------------------------------------------
# Exactness: the 2D DP matches the brute-force oracle
# ---------------------------------------------------------------------------

def test_dp_matches_brute_force_exhaustive():
    dims = [1, 2]
    for stages, initial, final in _sweep_instances(dims, 3, (2, 64, 8, 512)):
        _assert_dp_exact(stages, dims, (2, 2), initial, final)


def test_dp_matches_brute_force_3dims_asymmetric_grid():
    shape = (2, 8, 64, 8, 512)
    cases = [
        [frozenset({2}), frozenset({3}), frozenset({1}), frozenset({3})],
        [frozenset({1, 2}), frozenset(), frozenset({2, 3})],
        [frozenset({1}), frozenset({1}), frozenset({2})],
    ]
    for pattern in cases:
        stages = [Stage(f, f"s{i}", shape) for i, f in enumerate(pattern)]
        for initial, final in (((1, 2), (1, 2)), (None, None),
                               ((2, 2), None), (3, (1, 3))):
            _assert_dp_exact(stages, [1, 2, 3], (2, 4), initial, final)


# ---------------------------------------------------------------------------
# Hypothesis: the same properties over a wider randomized instance space
# ---------------------------------------------------------------------------

if _HAVE_HYPOTHESIS:
    @st.composite
    def stage_problems(draw, max_dims=3, max_stages=5):
        """Byte-weighted instances with extents every grid factor
        divides."""
        dims = list(range(1, 1 + draw(st.integers(2, max_dims))))
        stages = []
        for i in range(draw(st.integers(1, max_stages))):
            forbid = draw(st.sets(st.sampled_from(dims), min_size=0,
                                  max_size=len(dims) - 1))
            shape = tuple([2] + [draw(st.sampled_from([8, 64, 512]))
                                 for _ in range(max_dims)])
            stages.append(Stage(frozenset(forbid), f"s{i}", shape))
        initial = draw(st.one_of(st.none(), st.sampled_from(dims)))
        final = draw(st.one_of(st.none(), st.sampled_from(dims)))
        return stages, dims, initial, final

    @given(stage_problems(), st.sampled_from([2, 4]), st.booleans())
    @settings(max_examples=150, deadline=None)
    def test_degenerate_grid_collapse_property(problem, n, outer):
        stages, dims, initial, final = problem
        _assert_collapse(stages, dims, (n, 1) if outer else (1, n),
                         initial, final)

    @given(stage_problems(max_dims=3, max_stages=4),
           st.sampled_from([(2, 2), (2, 4)]))
    @settings(max_examples=60, deadline=None)
    def test_dp_matches_brute_force_property(problem, grid):
        stages, dims, initial, final = problem
        _assert_dp_exact(stages, dims, grid, initial, final)


# ---------------------------------------------------------------------------
# Units: feasibility, placement equality, schedule wrapper, sharder specs
# ---------------------------------------------------------------------------

def test_layout_allows_per_component_divisibility():
    # (B, T, S, C) = (2, 8, 4, 64) on a (2, 4) grid
    stage = Stage(frozenset({3}), "attn", (2, 8, 4, 64))
    assert layout_allows(stage, (1, 1), (2, 4))        # 8 % (2*4) == 0
    assert not layout_allows(stage, (2, 2), (2, 4))    # 4 % 8 != 0
    assert layout_allows(stage, (2, 1), (2, 4))        # 4 % 2, 8 % 4
    assert not layout_allows(stage, (1, 3), (2, 4))    # 3 is a compute dim
    assert not layout_allows(stage, (3, 3), (2, 4))
    assert layout_allows(stage, None, (2, 4))
    # size-1 axes contribute no factor
    assert layout_allows(stage, (2, 2), (1, 1))


def test_pair_placement_equal_ignores_size1_axes():
    assert pair_placement_equal((1, 2), (3, 2), (1, 4))
    assert not pair_placement_equal((1, 2), (1, 3), (1, 4))
    assert pair_placement_equal((1, 2), (1, 3), (2, 1))
    assert pair_placement_equal(1, (1, 1), (2, 4))     # int lifts to diagonal
    assert not pair_placement_equal((1, 2), (2, 1), (2, 4))
    assert pair_placement_equal(None, None, (2, 4))
    assert not pair_placement_equal(None, (1, 2), (2, 4))


def test_schedule2d_expected_collectives_and_periodic():
    from repro.core.schedule import Schedule2D, classify2

    stages = tuple(Stage(frozenset(), f"s{i}", (2, 8, 8, 64))
                   for i in range(4))
    layouts = ((1, 3), (1, 2), (2, 2), (1, 2))
    sched = Schedule2D(stages, layouts, grid=(2, 4), initial=(1, 2),
                       final=(1, 2))
    assert classify2((1, 2), (1, 3)).collective_counts() == {"all-to-all": 1}
    # joint diagonal change = ONE full-group primitive
    assert classify2((1, 1), (2, 2)).collective_counts() == {"all-to-all": 1}
    assert classify2((1, 1), (1, 1)).collective_counts() == {}
    assert classify2((1, 1), (None, None)).collective_counts() == {
        "all-gather": 1}
    total = sched.expected_collectives()
    assert set(total) == {"all-to-all"}
    # periodic() rejects a drifting plan
    bad = Schedule2D(stages, ((1, 2), (2, 2), (1, 2), (1, 2)), grid=(2, 4))
    with pytest.raises(ValueError, match="not periodic"):
        bad.periodic(2)
    per = Schedule2D(stages, ((1, 2), (2, 2)) * 2, grid=(2, 4),
                     initial=(1, 2), final=(1, 2)).periodic(2)
    assert per.wrap().collective_counts() == {"all-to-all": 1}


def test_sharder_layout_spec_two_axis_pspecs():
    from jax.sharding import PartitionSpec as P

    from repro.parallel.partition import ParallelPlan, Sharder

    sh = Sharder(mesh=None, plan=ParallelPlan(),
                 sp_axes=("sp_out", "sp_in"))
    # per-axis pair: component k shards tensor dim layout[k] over sp_axes[k]
    assert sh.layout_spec((1, 2), 4) == P("data", "sp_out", "sp_in", None)
    # diagonal (int) = the 1D embedding: one dim over the joint axis tuple
    assert sh.layout_spec(1, 4) == P("data", ("sp_out", "sp_in"), None, None)
    assert sh.layout_spec((2, 2), 4) == P("data", None,
                                          ("sp_out", "sp_in"), None)
    # None component replicates that axis; None layout replicates all
    assert sh.layout_spec((None, 2), 4) == P("data", None, "sp_in", None)
    assert sh.layout_spec(None, 3) == P("data", None, None)
    assert sh.layout_spec((1, 2), 4, batch_dim=None) == P(
        None, "sp_out", "sp_in", None)
    with pytest.raises(ValueError, match="components"):
        sh.layout_spec((1, 2, 3), 4)
    # the old hard-wired 3-dim special case is subsumed and gone
    assert not hasattr(Sharder, "channels3")


def test_mesh_topology_sp2d_detection_and_loud_unknown_axis():
    from repro.launch.mesh import make_mesh, mesh_topology

    mesh = make_mesh((1, 1), ("sp_out", "sp_in"))
    topo = mesh_topology(mesh)
    assert [a.name for a in topo.axes] == ["dcn", "ici"]
    assert topo.size == 1
    with pytest.raises(ValueError, match="no axis 'model'"):
        mesh_topology(mesh, sp_axis="model")


def test_plan2d_transformer2d_prefers_single_axis_switches():
    """The OpenSora-like cycle on a (2, 4) grid: the plan never crosses a
    boundary changing both axes non-jointly (the nmulti tie-break), and
    every planned collective is an all-to-all — the compiled contract the
    md_scenario pins on real devices."""
    from repro.core.schedule import Schedule2D

    # (B, T, S, C) = (2, 4, 8, 32) with 4 heads: the head extent rules the
    # T and head diagonals out on a (2, 4) grid, exactly the tiny t2d model
    # (models/transformer2d.stages2d) the md_scenario executes
    shape, ext = (2, 4, 8, 32), (2, 4, 8, 4)
    period = [Stage(frozenset({2}), "sp_attn", shape, extents=ext),
              Stage(frozenset({3}), "sp_mlp", shape, extents=ext),
              Stage(frozenset({1}), "t_attn", shape, extents=ext),
              Stage(frozenset({3}), "t_mlp", shape, extents=ext)]
    # Solve ONE period with entry = exit = the carried layout and tile —
    # every stage holds the same bytes, so this is the steady state (and the
    # unrolled DP's equal-cost tie-breaks are free to drift off-period,
    # which is why models/transformer2d.dsp2d_schedule plans the same way).
    body = plan_switches_2d(period, [1, 2, 3], grid=(2, 4), initial=(1, 2),
                            final=(1, 2))
    assert body == [(1, 3), (1, 2), (2, 2), (1, 2)]
    sched = Schedule2D(tuple(period * 2), tuple(body * 2), grid=(2, 4),
                       initial=(1, 2), final=(1, 2))
    for tr in sched.transitions():
        changed = sum(s != t for s, t in zip(tr.src, tr.tgt))
        assert tr.joint or changed <= 1, (tr.src, tr.tgt)
        assert set(tr.collective_counts()) <= {"all-to-all"}
    # periodic steady state: period 4, carry = entry layout
    per = sched.periodic(4)
    assert pair_placement_equal(sched.layouts[-1], (1, 2), (2, 4))
    assert per.wrap().collective_counts() == {"all-to-all": 1}
    assert sched.expected_collectives() == {"all-to-all": 8}
