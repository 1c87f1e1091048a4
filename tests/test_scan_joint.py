"""Planned backward under ``jax.lax.scan`` — the PR-5 test tier.

The scanned LM and enc-dec forwards consume non-mirrored joint plans
through the Sharder's per-period custom_vjp boundaries
(``core.schedule.planned_constraint``; docs/architecture.md §3.5).  These
tests pin the acceptance properties that run on ONE device (the executed
custom_vjp machinery is identical; only the collectives degenerate):

* gradient parity: a scanned-LM / enc-dec training step under a FORCED
  non-mirrored joint plan produces gradients bit-identical (fp32) to the
  mirrored reference — the planned backward is layout-only, never math;
* the Sharder actually derives (and validates) the backward class layouts;
* the executed-leg accounting (``ScheduleExecutor.expected_bwd_collectives``)
  prices the scan structure the 8-device HLO tier measures
  (tests/test_hlo_collectives.py compiles the same cases on 8 devices);
* a ``brute_force_joint``-vs-DP property test over random per-period
  extents (hypothesis, importorskip-guarded below, so the file stays
  collectable without it).

The 8-device parity scenario (sharded vs unsharded, forced vs mirrored)
lives in tests/md_scenarios.py::scenario_scan_joint_bwd_parity.
"""
import numpy as np
import pytest

from repro.core.plan import Stage, brute_force_joint, joint_cost_bytes, plan_joint
from repro.core.schedule import Schedule, ScheduleExecutor


def _grad_leaves(tree):
    import jax
    return jax.tree_util.tree_leaves(tree)


def _assert_bitwise(a_tree, b_tree):
    for a, b in zip(_grad_leaves(a_tree), _grad_leaves(b_tree)):
        assert (np.asarray(a) == np.asarray(b)).all(), "gradient mismatch"


# ---------------------------------------------------------------------------
# Gradient parity: scanned LM / enc-dec under a forced non-mirrored plan
# ---------------------------------------------------------------------------

def _lm_setup():
    import jax
    import jax.numpy as jnp
    from repro.models.lm import LMConfig, init_lm
    cfg = LMConfig(name="t", n_layers=2, d_model=32, n_heads=4, n_kv_heads=4,
                   head_dim=8, d_ff=64, vocab=64, dtype=jnp.float32)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    return cfg, params, {"tokens": toks, "labels": toks}


def test_scanned_lm_forced_nonmirrored_gradient_parity():
    """The scanned LM trains under a forced non-mirrored joint plan and the
    gradients are BIT-identical to the mirrored reference: the per-period
    custom_vjp boundaries change cotangent layouts, never values.  Fails if
    ``require_mirrored=True`` (or plain, bwd-ignorant constraints) come
    back — the forced plan would then silently execute the mirror, and the
    schedule handed to the Sharder would no longer carry ``bwd_dims``."""
    import jax
    from repro.launch.mesh import make_mesh
    from repro.models.lm import dsp_schedule, lm_loss
    from repro.parallel.partition import ParallelPlan, make_sharder
    cfg, params, batch = _lm_setup()
    mesh = make_mesh((1, 1), ("data", "model"))
    plan = ParallelPlan(mode="dsp", shard_vocab=False)

    def grads(sched):
        sharder = make_sharder(mesh, plan, schedule=sched)
        return jax.jit(jax.grad(lambda p: lm_loss(
            p, batch, cfg, sharder=sharder, backend="ref",
            remat=False)[0]))(params)

    mirrored = dsp_schedule(cfg, 1, seq=16, batch=2, joint=True)
    assert mirrored.mirrored          # forced stage graph: DP keeps mirror
    # per-period pattern (proj, attn, ffn) -> all-channel backward
    forced = dsp_schedule(cfg, 1, seq=16, batch=2, joint=True,
                          bwd_dims=(2, 2, 2))
    assert not forced.mirrored
    # the sharder really derives the planned backward class layouts
    sh = make_sharder(mesh, plan, schedule=forced)
    assert (sh.bwd_resid_dim, sh.bwd_mixer_dim) == (2, 2)
    assert sh.bwd_entry_dim == 1 and sh.bwd_carry_dim == 2
    _assert_bitwise(grads(mirrored), grads(forced))


def test_scanned_lm_forced_parity_with_remat():
    """Same contract through ``jax.checkpoint`` — the recompute re-emits the
    forward constraints, the planned backward still only moves layouts."""
    import jax
    from repro.launch.mesh import make_mesh
    from repro.models.lm import dsp_schedule, lm_loss
    from repro.parallel.partition import ParallelPlan, make_sharder
    cfg, params, batch = _lm_setup()
    mesh = make_mesh((1, 1), ("data", "model"))
    plan = ParallelPlan(mode="dsp", shard_vocab=False)

    def grads(sched):
        sharder = make_sharder(mesh, plan, schedule=sched)
        return jax.jit(jax.grad(lambda p: lm_loss(
            p, batch, cfg, sharder=sharder, backend="ref",
            remat=True)[0]))(params)

    mirrored = dsp_schedule(cfg, 1, seq=16, batch=2, joint=True)
    forced = dsp_schedule(cfg, 1, seq=16, batch=2, joint=True,
                          bwd_dims=(2, 2, 2))
    _assert_bitwise(grads(mirrored), grads(forced))


def test_encdec_forced_nonmirrored_gradient_parity():
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.models.encdec import (EncDecConfig, dsp_schedule, encdec_loss,
                                     init_encdec)
    from repro.parallel.partition import ParallelPlan, make_sharder
    cfg = EncDecConfig(name="t", n_enc_layers=2, n_dec_layers=2, d_model=32,
                       n_heads=4, n_kv_heads=4, head_dim=8, d_ff=64,
                       vocab=64, dtype=jnp.float32)
    params = init_encdec(jax.random.PRNGKey(0), cfg)
    batch = {"feats": jax.random.normal(jax.random.PRNGKey(1),
                                        (2, 16, cfg.frontend_dim)),
             "tokens": jax.random.randint(jax.random.PRNGKey(2), (2, 8),
                                          0, 64),
             "labels": jax.random.randint(jax.random.PRNGKey(3), (2, 8),
                                          0, 64)}
    mesh = make_mesh((1, 1), ("data", "model"))
    plan = ParallelPlan(mode="dsp", shard_vocab=False)

    def grads(sched):
        sharder = make_sharder(mesh, plan, schedule=sched)
        return jax.jit(jax.grad(lambda p: encdec_loss(
            p, batch, cfg, sharder=sharder, backend="ref",
            remat=False)[0]))(params)

    mirrored = dsp_schedule(cfg, 1, s_enc=16, s_dec=8, batch=2, joint=True)
    assert mirrored.mirrored
    # class-uniform forced backward: every stage's cotangent on dim 2
    forced = dsp_schedule(cfg, 1, s_enc=16, s_dec=8, batch=2, joint=True,
                          bwd_dims=(2,) * len(mirrored.dims))
    assert not forced.mirrored
    _assert_bitwise(grads(mirrored), grads(forced))


# ---------------------------------------------------------------------------
# Sharder backward-plan validation
# ---------------------------------------------------------------------------

def test_sharder_rejects_class_divergent_backward_plan():
    """One backward layout per stage class — a per-stage-divergent backward
    plan cannot be expressed through the hook path and must fail loudly."""
    import jax.numpy as jnp
    from repro.models.lm import LMConfig, dsp_schedule
    from repro.parallel.partition import ParallelPlan, make_sharder
    cfg = LMConfig(name="t", n_layers=2, d_model=32, n_heads=4, n_kv_heads=4,
                   head_dim=8, d_ff=64, vocab=64, dtype=jnp.float32)
    # proj backward on 2 but ffn backward on 1: both are resid-class stages
    sched = dsp_schedule(cfg, 1, seq=16, batch=2, joint=True,
                         bwd_dims=(2, 2, 1))
    with pytest.raises(ValueError, match="one backward layout per"):
        make_sharder(None, ParallelPlan(mode="dsp"), schedule=sched)


def test_lm_dsp_schedule_rejects_non_periodic_forced_backward():
    import jax.numpy as jnp
    from repro.models.lm import LMConfig, dsp_schedule, stage_period, stages
    cfg = LMConfig(name="t", n_layers=2, d_model=32, n_heads=4, n_kv_heads=4,
                   head_dim=8, d_ff=64, vocab=64, dtype=jnp.float32)
    n_stages = len(stages(cfg))
    assert stage_period(cfg) == 3 and n_stages == 6
    bad = (2,) * (n_stages - 1) + (1,)       # full-length, not periodic
    with pytest.raises(ValueError, match="periodic"):
        dsp_schedule(cfg, 1, seq=16, batch=2, joint=True, bwd_dims=bad)


# ---------------------------------------------------------------------------
# Executed-leg accounting (what the 8-device HLO tier measures)
# ---------------------------------------------------------------------------

def _free_periodic(dims, bwd, *, initial, final):
    st = tuple(Stage(frozenset(), f"s{i}") for i in range(len(dims)))
    return Schedule(st, tuple(dims), initial=initial, final=final,
                    bwd_dims=bwd)


def test_expected_bwd_collectives_periodic_accounting():
    """Pins the executed scan-backward structure: seam + carry-init once,
    reversed boundaries + wrap per period, input-grad entry once.  The same
    numbers are compiled and counted on 8 devices by
    tests/test_hlo_collectives.py (synthetic scan worker cases)."""
    from repro.core.layout import from_mesh
    from repro.launch.mesh import make_mesh
    ctx = from_mesh(make_mesh((1, 1), ("data", "model")))
    P = 3

    def a2a(sched, carry="first"):
        ex = ScheduleExecutor(sched.periodic(2), backend="auto", ctx=ctx)
        return ex.expected_bwd_collectives(P, carry=carry).get("all-to-all",
                                                                0)

    # mirrored: the transposed forward (2 switches/period, free ends)
    mir = _free_periodic((1, 2) * P, None, initial=1, final=1)
    assert a2a(mir) == a2a(mir, "last") == 2 * P
    # non-mirrored, seam/entry free: swap plan — 2/period + carry-init + entry;
    # a carry held in bwd[-1] = initial needs neither
    swap = _free_periodic((1, 2) * P, (2, 1) * P, initial=1, final=1)
    assert a2a(swap) == 2 * P + 2
    assert a2a(swap, "last") == 2 * P
    # forward parks on a third dim; backward alternates: seam + carry-init +
    # 2/period + entry (no carry-init when the carry sits in bwd[-1])
    park = _free_periodic((3,) * (2 * P), (1, 2) * P, initial=3, final=3)
    assert a2a(park) == 2 * P + 3
    assert a2a(park, "last") == 2 * P + 2
    # steady-state class-uniform plan (period starts/ends on the same bwd
    # layout): carry-init and wrap are keeps — only the seam + entry remain
    flat = _free_periodic((1, 2) * P, (2, 2) * P, initial=1, final=1)
    assert a2a(flat) == a2a(flat, "last") == 2


def test_periodic_bwd_views():
    sched = _free_periodic((1, 2) * 2, (2, 1) * 2, initial=1, final=1)
    ps = sched.periodic(2)
    assert ps.bwd_dims == (2, 1)
    assert ps.bwd_seam().kind == "keep"            # final 1 -> bwd[-1] 1
    assert ps.bwd_boundary(1).kind == "switch"     # bwd[1]=1 -> bwd[0]=2
    assert ps.bwd_wrap().kind == "switch"          # bwd[0]=2 -> bwd[-1]=1
    assert ps.bwd_enter().kind == "switch"         # bwd[0]=2 -> initial 1


def test_schedule_periodic_validates_backward_leg():
    st = tuple(Stage(frozenset(), f"s{i}") for i in range(4))
    sched = Schedule(st, (1, 2, 1, 2), initial=1, final=1,
                     bwd_dims=(2, 1, 1, 2))
    with pytest.raises(ValueError, match="backward plan"):
        sched.periodic(2)


# ---------------------------------------------------------------------------
# Joint DP vs brute force over random per-period extents (hypothesis).
# Guarded per-test (not module-level importorskip): the parity/accounting
# tests above must run on hypothesis-free environments too.
# ---------------------------------------------------------------------------

try:
    import hypothesis.strategies as hst
    from hypothesis import given, settings
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    @hst.composite
    def periodic_joint_problems(draw):
        """Scan-style instances: a random per-period stage pattern repeated
        ``n_periods`` times, with random per-period activation/grad extents
        — the byte asymmetries that make the joint DP diverge from the
        mirror."""
        dims = list(range(1, draw(hst.integers(2, 3)) + 1))
        period = draw(hst.integers(1, 2))
        n_periods = draw(hst.integers(1, 3))
        pattern = []
        for i in range(period):
            forbid = draw(hst.sets(hst.sampled_from(dims),
                                   max_size=len(dims) - 1))
            fwd_ext = draw(hst.sampled_from([4, 64, 512]))
            bwd_ext = draw(hst.sampled_from([4, 64, 512]))
            pattern.append((frozenset(forbid), (1, fwd_ext, 8),
                            (1, bwd_ext, 8)))
        stages = []
        for p in range(n_periods):
            for i, (forbid, fs, bs) in enumerate(pattern):
                stages.append(Stage(forbid, f"p{p}s{i}", fs, 2, bs, 2))
        initial = draw(hst.sampled_from([None] + dims))
        final = draw(hst.sampled_from([None] + dims))
        return stages, dims, initial, final

    @settings(max_examples=40, deadline=None)
    @given(periodic_joint_problems())
    def test_joint_dp_matches_brute_force_on_periodic_instances(problem):
        stages, dims, initial, final = problem
        jp = plan_joint(stages, dims, n=4, initial=initial, final=final)
        cost = joint_cost_bytes(stages, jp, n=4, initial=initial,
                                final=final).total
        oracle = brute_force_joint(stages, dims, n=4, initial=initial,
                                   final=final)
        assert cost == pytest.approx(oracle)
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_joint_dp_matches_brute_force_on_periodic_instances():
        pass
