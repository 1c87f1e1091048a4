"""Joint forward+backward planner and planned-backward executor tests.

The backward pass is a first-class stage graph (``core.plan.plan_joint``):
these tests pin the three acceptance properties — a uniform mesh reproduces
the mirrored plan exactly, an asymmetric ICI x DCN instance gets a strictly
cheaper round trip than the mirrored-forward plan, and gradients through the
planned-backward executor match the mirrored path — plus the non-periodic
(unrolled) execution view.  No optional deps; runs everywhere.
"""
import random

import pytest

from repro.core.plan import (JointPlan, Stage, brute_force_joint,
                             joint_cost_bytes, joint_cost_seconds,
                             plan_joint, plan_switches_dp)
from repro.core.schedule import (Schedule, ScheduleExecutor, UnrolledSchedule,
                                 plan_joint_schedule)
from repro.core.topology import Topology


def _t2d_like(n_pairs, shape=(2, 16, 32, 8)):
    out = []
    for i in range(n_pairs):
        out.append(Stage(frozenset({2}), f"l{i}.spatial", shape))
        out.append(Stage(frozenset({1}), f"l{i}.temporal", shape))
    return out


# ---------------------------------------------------------------------------
# Joint DP: uniform => mirror, exactness, asymmetric => strictly cheaper
# ---------------------------------------------------------------------------

def test_joint_uniform_reproduces_mirror_exactly():
    """Uniform mesh / symmetric bytes: the joint DP must return the
    mirrored plan bit-for-bit — same forward as the fwd-only DP, backward
    retracing it."""
    st = _t2d_like(3)
    for topo in (None, Topology.uniform(8)):
        jp = plan_joint(st, [1, 2], n=8, initial=1, final=1, topology=topo)
        fwd_only = tuple(plan_switches_dp(st, [1, 2], n=8, initial=1,
                                          final=1, topology=topo))
        assert jp.mirrored
        assert jp.fwd == fwd_only
        assert jp.bwd == fwd_only
    # and the schedule wrapper drops bwd_dims for mirrored plans
    sched = plan_joint_schedule(st, [1, 2], n=8, initial=1, final=1)
    assert sched.bwd_dims is None and sched.mirrored


def test_joint_cost_splits_legs():
    st = _t2d_like(2)
    sched = plan_joint_schedule(st, [1, 2], n=8, initial=1, final=1)
    rb = sched.roundtrip_bytes(8)
    # symmetric instance: the bwd leg prices exactly like the fwd leg
    assert rb.fwd == pytest.approx(sched.per_device_bytes(8))
    assert rb.bwd == pytest.approx(rb.fwd)
    assert rb.total == pytest.approx(rb.fwd + rb.bwd)


def test_joint_dp_exact_vs_brute_force_random():
    """The joint DP must match the exponential round-trip oracle on random
    byte-weighted instances with fwd/bwd asymmetric shapes."""
    rng = random.Random(7)
    for trial in range(60):
        dims = list(range(1, rng.randint(2, 3) + 1))
        stages = []
        for i in range(rng.randint(1, 4)):
            forbid = set(rng.sample(dims, rng.randint(0, len(dims) - 1)))
            fwd = (1, rng.choice([4, 256]), 8)
            bwd = (1, rng.choice([4, 256]), 8)
            stages.append(Stage(frozenset(forbid), f"s{i}", fwd, 2, bwd, 2))
        initial = rng.choice([None] + dims)
        final = rng.choice([None] + dims)
        jp = plan_joint(stages, dims, n=4, initial=initial, final=final)
        cost = joint_cost_bytes(stages, jp, n=4, initial=initial,
                                final=final).total
        oracle = brute_force_joint(stages, dims, n=4, initial=initial,
                                   final=final)
        assert cost == pytest.approx(oracle), (trial, jp)


def test_joint_dp_exact_with_coupling():
    """With residual coupling (no-remat), deviating from the forward layout
    costs a re-shard — the DP must still match the oracle and deviate less
    often."""
    small, big = (1, 4, 8), (1, 1024, 8)
    st = [Stage(frozenset(), "s0", small, 2, big, 2),
          Stage(frozenset({1}), "s1", big, 2, small, 2),
          Stage(frozenset(), "s2", small, 2, big, 2)]
    for couple in (False, True):
        jp = plan_joint(st, [1, 2], n=4, initial=1, final=1, couple=couple)
        c = joint_cost_bytes(st, jp, n=4, initial=1, final=1,
                             couple=couple).total
        assert c == pytest.approx(brute_force_joint(
            st, [1, 2], n=4, initial=1, final=1, couple=couple))


def test_joint_beats_mirror_on_asymmetric_ici_dcn():
    """REGRESSION (acceptance): on an asymmetric ICI x DCN fabric with
    fwd/bwd byte asymmetry, the joint DP's planned round-trip seconds are
    STRICTLY lower than the mirrored-forward plan's — the joint DP may even
    pick a forward that the fwd-only DP would reject, because the round
    trip, not the forward leg, is the objective."""
    topo = Topology.multihost(2, 4, placement={1: ("dcn",), 2: ("dcn",),
                                               4: ("dcn",)})
    tiny, huge = (1, 4, 8), (1, 4096, 8)
    st = [Stage(frozenset(), "s0", huge, 2, tiny, 2),
          Stage(frozenset({2, 4}), "s1", huge, 2, tiny, 2),
          Stage(frozenset(), "s2", tiny, 2, tiny, 2)]
    dims = [1, 2, 3, 4]
    jp = plan_joint(st, dims, initial=2, final=4, topology=topo)
    mirror_fwd = tuple(plan_switches_dp(st, dims, n=topo.size, initial=2,
                                        final=4, topology=topo))
    mirror = JointPlan(mirror_fwd, mirror_fwd)
    jc = joint_cost_seconds(st, jp, topo, initial=2, final=4).total
    mc = joint_cost_seconds(st, mirror, topo, initial=2, final=4).total
    assert not jp.mirrored
    assert jc < mc * (1 - 1e-6)              # strictly cheaper round trip
    assert jc == pytest.approx(brute_force_joint(
        st, dims, initial=2, final=4, topology=topo))
    # the schedule wrapper carries the planned backward in this case
    sched = plan_joint_schedule(st, dims, initial=2, final=4, topology=topo)
    assert sched.bwd_dims is not None and not sched.mirrored
    rs = sched.roundtrip_seconds()
    assert rs.total == pytest.approx(jc)


def test_bwd_transitions_accounting():
    st = _t2d_like(2)
    sched = plan_joint_schedule(st, [1, 2], n=8, initial=1, final=1)
    trs = sched.bwd_transitions()
    # seam keep (loss on T, last bwd stage on... dims (1,2,1,2): seam from
    # final=1 into bwd[-1]=2 is a switch), then reverse boundaries
    kinds = [t.kind for t in trs]
    assert kinds[0] == "switch"              # seam: 1 -> 2
    assert len(trs) == len(sched.dims) + 1
    # mirrored: bwd leg has the same switch count as the fwd leg
    n_fwd = sched.n_switches()
    n_bwd = sum(1 for t in trs if t.kind == "switch")
    assert n_bwd == n_fwd


# ---------------------------------------------------------------------------
# Non-periodic (unrolled) schedules
# ---------------------------------------------------------------------------

def test_unrolled_schedule_view():
    """A plan that parks on a hot dim mid-sequence is non-periodic: the
    periodic view must reject it (with a pointer to unrolled()) and the
    unrolled view must expose every absolute boundary."""
    st = [Stage(frozenset({1}), "a"), Stage(frozenset({2}), "b"),
          Stage(frozenset({1}), "c"), Stage(frozenset({1}), "d")]
    ns = Schedule(tuple(st), (2, 1, 3, 3), initial=2)
    with pytest.raises(ValueError, match="unrolled"):
        ns.periodic(2)
    un = ns.unrolled()
    assert un.n_stages == 4
    assert [un.boundary(t).kind for t in (1, 2, 3)] == \
        ["switch", "switch", "keep"]
    assert un.enter().kind == "keep" and un.exit().kind == "keep"
    ex = ScheduleExecutor(un, backend="explicit")
    assert ex.expected_collectives() == {"all-to-all": 2}
    with pytest.raises(ValueError, match="wrap"):
        ex.wrap(object())


def test_unrolled_t2d_forward_matches_scan():
    """The model executor must run an injected unrolled schedule and
    reproduce the scanned path exactly (same plan, different execution)."""
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.models.transformer2d import (T2DConfig, dsp_schedule, forward,
                                            init_t2d)
    cfg = T2DConfig(name="t", n_layers=4, d_model=32, n_heads=4, d_ff=64,
                    in_dim=8, dtype=jnp.float32)
    params = init_t2d(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 8, 8))
    t = jax.random.uniform(jax.random.PRNGKey(2), (2,))
    mesh = make_mesh((1, 1), ("data", "model"))
    ps = dsp_schedule(cfg, 1, t_len=4, s_len=8, batch=2)
    ref = forward(params, x, t, cfg, mesh=mesh, backend="ref", remat=False)
    un = forward(params, x, t, cfg, mesh=mesh, backend="ref", remat=False,
                 schedule=ps.schedule.unrolled())
    assert jnp.allclose(un, ref)
    un_remat = forward(params, x, t, cfg, mesh=mesh, backend="ref",
                       remat=True, schedule=ps.schedule.unrolled())
    assert jnp.allclose(un_remat, ref)


# ---------------------------------------------------------------------------
# Planned-backward executor (custom_vjp)
# ---------------------------------------------------------------------------

def _parity_instance():
    """3-dim chain where the planned backward is feasibly non-mirrored."""
    st = (Stage(frozenset({1}), "a"), Stage(frozenset({2}), "b"),
          Stage(frozenset({1}), "c"))
    planned = Schedule(st, (3, 3, 3), initial=1, final=1, bwd_dims=(2, 1, 2))
    mirror = Schedule(st, (3, 3, 3), initial=1, final=1)
    return planned, mirror


def test_explicit_backend_rejects_planned_backward():
    planned, _ = _parity_instance()
    with pytest.raises(ValueError, match="mirrored backward"):
        ScheduleExecutor(planned.unrolled(), backend="explicit")


def test_planned_backward_gradient_parity():
    """Gradients through the planned-backward executor (custom_vjp per
    boundary) must match the mirrored path — the constraints are layout
    only, never math."""
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.core.layout import from_mesh
    planned, mirror = _parity_instance()
    mesh = make_mesh((1, 1), ("data", "model"))
    ctx = from_mesh(mesh)

    def make_loss(sched):
        ex = ScheduleExecutor(sched.unrolled(), backend="auto", ctx=ctx)

        def loss(w, x):
            x = ex.enter(x)
            x = x * w
            x = ex.boundary(x, 1)
            x = jnp.sin(x)
            x = ex.anchor(x, 1)
            x = ex.boundary(x, 2)
            x = x * w
            x = ex.exit(x)
            return jnp.sum(x ** 2)
        return loss

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 4, 4))
    w = jnp.float32(1.3)
    gp = jax.jit(jax.grad(make_loss(planned)))(w, x)
    gm = jax.jit(jax.grad(make_loss(mirror)))(w, x)
    assert jnp.allclose(gp, gm)


def test_planned_backward_t2d_loss_gradient_parity():
    """End-to-end: t2d training loss gradients are identical whether the
    backward mirrors the forward or runs through the planned-backward
    executor machinery (joint=True solves the mirror here — symmetric model
    — so also inject a synthetic bwd_dims to force the custom_vjp path)."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.models.transformer2d import (T2DConfig, dsp_schedule, init_t2d,
                                            t2d_loss)
    cfg = T2DConfig(name="t", n_layers=2, d_model=32, n_heads=4, d_ff=64,
                    in_dim=8, dtype=jnp.float32)
    params = init_t2d(jax.random.PRNGKey(0), cfg)
    batch = {"x": jax.random.normal(jax.random.PRNGKey(1), (2, 4, 8, 8)),
             "t": jax.random.uniform(jax.random.PRNGKey(2), (2,)),
             "target": jax.random.normal(jax.random.PRNGKey(3), (2, 4, 8, 8))}
    mesh = make_mesh((1, 1), ("data", "model"))

    def grads(**kw):
        return jax.grad(lambda p: t2d_loss(p, batch, cfg, mesh=mesh,
                                           backend="ref", remat=False,
                                           **kw)[0])(params)

    g_ref = grads()
    g_joint = grads(joint=True)
    # force a (mirrored-layout but custom_vjp-executed) planned backward
    ps = dsp_schedule(cfg, 1, t_len=4, s_len=8, batch=2)
    forced = dataclasses.replace(ps.schedule, bwd_dims=ps.schedule.dims[::-1]
                                 if ps.schedule.dims[::-1] != ps.schedule.dims
                                 else ps.schedule.dims)
    g_planned = grads(schedule=forced.unrolled())
    for ga, gb in ((g_ref, g_joint), (g_ref, g_planned)):
        flat_a = jax.tree_util.tree_leaves(ga)
        flat_b = jax.tree_util.tree_leaves(gb)
        for a, b in zip(flat_a, flat_b):
            assert jnp.allclose(a, b, atol=1e-5), "gradient mismatch"


def test_periodic_planned_backward_seam_targets_last_stage(monkeypatch):
    """REGRESSION: for a PERIODIC planned-backward schedule the exit's
    backward constraint is the seam — it must target bwd_plan[-1] (==
    bwd_plan[period-1]) so the subsequent wrap backward is a free keep;
    targeting bwd_plan[0] would emit two collectives where the cost model
    prices one."""
    import repro.core.schedule as schedule_mod
    from repro.launch.mesh import make_mesh
    from repro.core.layout import from_mesh

    # free stages over 3 dims: fwd parks on 3, bwd alternates 1/2 — feasible,
    # non-mirrored, and periodic with period 2
    st = tuple(Stage(frozenset(), f"s{i}") for i in range(4))
    sched = Schedule(st, (3, 3, 3, 3), initial=3, final=3,
                     bwd_dims=(1, 2, 1, 2))
    ps = sched.periodic(2)

    recorded = []

    def record(x, fwd_sharding, bwd_sharding):
        recorded.append(bwd_sharding.spec)
        return x

    monkeypatch.setattr(schedule_mod, "_planned_constraint", record)
    mesh = make_mesh((1, 1), ("data", "model"))
    ex = ScheduleExecutor(ps, backend="auto", ctx=from_mesh(mesh))
    import jax.numpy as jnp
    x = jnp.zeros((2, 4, 4, 4))
    ex.exit(x)
    ex.wrap(x)
    # exit seam -> bwd_plan[-1] (dim 2 sharded on "model"); wrap -> same
    assert recorded[0][2] == "model", recorded[0]
    assert recorded[0] == recorded[1]


def test_lm_joint_runs_the_joint_dp_for_real(monkeypatch):
    """REGRESSION (PR 5): the scanned LM executes non-mirrored joint plans
    (per-period custom_vjp boundaries through the Sharder hooks), so
    ``dsp_schedule(joint=True)`` must run the joint DP — reintroducing
    ``require_mirrored=True`` fails this test.  On the LM's forced stage
    graph (each stage admits exactly one dim) the DP keeps the mirror, and
    the executed forward stays the fwd-only optimum."""
    import jax.numpy as jnp
    import repro.core.schedule as schedule_mod
    from repro.models.lm import LMConfig, dsp_schedule, stages
    cfg = LMConfig(name="t", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                   head_dim=16, d_ff=128, vocab=64, dtype=jnp.float32)
    from repro.core.plan import plan_switches_dp
    seen = []
    real = schedule_mod.plan_joint

    def spy(*a, **kw):
        seen.append(kw.get("require_mirrored", False))
        return real(*a, **kw)

    monkeypatch.setattr(schedule_mod, "plan_joint", spy)
    sched = dsp_schedule(cfg, 8, seq=64, batch=2, joint=True)
    # the joint DP actually ran (no forced-mirror shortcut) ...
    assert seen and seen[0] is False
    # ... and on this forced graph it keeps the mirror, fwd-optimal
    assert sched.mirrored
    fwd_only = tuple(plan_switches_dp(stages(cfg, seq=64, batch=2), (1, 2),
                                      n=8, initial=1, final=1))
    assert sched.dims == fwd_only


# ---------------------------------------------------------------------------
# Model-level joint schedules
# ---------------------------------------------------------------------------

def test_lm_joint_schedule_mirrored_on_symmetric():
    import jax.numpy as jnp
    from repro.models.lm import LMConfig, dsp_schedule
    cfg = LMConfig(name="t", n_layers=4, d_model=64, n_heads=4, n_kv_heads=2,
                   head_dim=16, d_ff=128, vocab=64, dtype=jnp.float32)
    sched = dsp_schedule(cfg, 8, seq=64, batch=2, joint=True)
    assert sched.mirrored                    # symmetric instance: mirror
    rb = sched.roundtrip_bytes(8)
    assert rb.bwd == pytest.approx(rb.fwd)


def test_encdec_joint_schedule():
    import jax.numpy as jnp
    from repro.models.encdec import EncDecConfig, dsp_schedule
    cfg = EncDecConfig(name="t", n_enc_layers=2, n_dec_layers=2, d_model=64,
                       n_heads=4, n_kv_heads=4, head_dim=16, d_ff=128,
                       vocab=64, dtype=jnp.float32)
    sched = dsp_schedule(cfg, 8, s_enc=64, s_dec=16, batch=2, joint=True)
    # enc-dec byte asymmetry is fwd==bwd symmetric, so the mirror stays
    assert sched.mirrored
    assert sched.roundtrip_bytes(8).total == pytest.approx(
        2 * sched.per_device_bytes(8))
