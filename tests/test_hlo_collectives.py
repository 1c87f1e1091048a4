"""The compiled-HLO contract of the plan-driven executor (referenced by
core/dsp.py): for the SAME planned schedule, the auto path (sharding
constraints under jit) and the explicit path (collectives inside shard_map)
must both compile to EXACTLY one all-to-all per planned switch, and the
``split`` primitive to zero collectives.

PR 5 extends the contract to the TRAIN step, per leg: on the scanned t2d
train step (both backends, mirrored joint plan as the control case) the
compiled grad shows exactly one all-to-all per planned forward switch plus
one per planned backward switch; on a synthetic scanned executor program
the same holds for FORCED non-mirrored joint plans (the per-period
custom_vjp backward), with counts matching
``ScheduleExecutor.expected_bwd_collectives``; and on the scanned-LM train
step the planned backward provably reaches the compiler (forward leg
invariant, backward leg changes with the plan).

PR 6 adds the comm-compute overlap contract: under
``overlap="chunked"|"double_buffer"`` each planned switch lowers to n-1
independent collective-permute hops (zero all-to-all) that span the
consuming kernel's compute, with output AND gradient parity pinned bitwise
against the synchronous executor.

Runs the compile in a subprocess with 8 simulated CPU devices so the main
pytest process keeps its 1-device default (same pattern as
tests/test_multidevice.py).
"""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(__file__)
SRC = os.path.join(HERE, "..", "src")


@pytest.fixture(scope="module")
def hlo_counts():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"     # simulated devices, never the chip
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "_hlo_worker.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, (
        f"HLO worker failed:\nSTDOUT:\n{proc.stdout}\n"
        f"STDERR:\n{proc.stderr[-4000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_planned_switch_count_is_table3(hlo_counts):
    # 2 switches per layer pair (paper §4.1 / Table 3), nothing else
    planned = hlo_counts["planned"]
    assert planned == {"all-to-all": 2 * hlo_counts["n_periods"]}


def test_auto_path_matches_plan(hlo_counts):
    """XLA SPMD must lower each planned switch to exactly one all-to-all."""
    auto = hlo_counts["auto"]
    planned = hlo_counts["planned"]
    assert auto.get("all-to-all", 0) == planned["all-to-all"], hlo_counts
    # no stray gathers from the constraint path
    assert auto.get("all-gather", 0) == 0, hlo_counts


def test_explicit_path_matches_plan(hlo_counts):
    """The explicit backend issues the collectives itself — count must equal
    the SAME plan the auto path executed (one executor, two backends)."""
    explicit = hlo_counts["explicit"]
    planned = hlo_counts["planned"]
    assert explicit.get("all-to-all", 0) == planned["all-to-all"], hlo_counts
    assert explicit.get("all-gather", 0) == 0, hlo_counts


def test_split_is_communication_free(hlo_counts):
    """Paper Table 2: s_hat -> s_i is a local slice — zero collectives."""
    assert hlo_counts["split"] == {}, hlo_counts


# ---------------------------------------------------------------------------
# Train-step per-leg contract (PR 5)
# ---------------------------------------------------------------------------

def _a2a(c):
    return c.get("all-to-all", 0)


def test_t2d_train_step_per_leg_counts(hlo_counts):
    """Scanned t2d train step, mirrored joint plan (the control case):
    forward leg == planned forward switches; the grad compile adds exactly
    the planned backward leg — on BOTH backends."""
    tr = hlo_counts["t2d_train"]
    assert tr["mirrored"]                      # symmetric model: DP keeps it
    assert _a2a(tr["fwd"]) == _a2a(tr["planned_fwd"]), tr
    assert _a2a(tr["grad"]) == _a2a(tr["fwd"]) + _a2a(tr["planned_bwd"]), tr
    # explicit backend: the mirrored transpose re-emits each collective once
    assert _a2a(tr["explicit_fwd"]) == _a2a(tr["planned_fwd"]), tr
    assert _a2a(tr["explicit_grad"]) == \
        _a2a(tr["explicit_fwd"]) + _a2a(tr["planned_bwd"]), tr


def test_planned_switches_carry_the_dsp_switch_scope(hlo_counts):
    """Every all-to-all of the planned forward carries the executor's
    ``dsp_switch`` scope (``repro.tracing``), and so does the train step's
    forward leg.  On the mirrored plan the transposed boundary constrains
    the cotangent to the layout it already has, so each backward switch
    lands on the first block-end anchor the cotangent meets: it carries
    that block's scope under ``transpose(``."""
    planned = hlo_counts["planned"]["all-to-all"]
    assert _a2a(hlo_counts["auto_switch"]) == _a2a(hlo_counts["auto"])
    assert _a2a(hlo_counts["auto_switch"]) == planned
    tr = hlo_counts["t2d_train"]
    assert _a2a(tr["grad_switch"]) == _a2a(tr["planned_fwd"]), tr
    assert _a2a(tr["grad_block_anchor_bwd"]) == _a2a(tr["planned_bwd"]), tr
    assert (_a2a(tr["grad_switch"]) + _a2a(tr["grad_block_anchor_bwd"])
            == _a2a(tr["grad"])), tr


def test_synthetic_scan_planned_backward_per_leg_counts(hlo_counts):
    """A scan-periodic schedule with distinct bwd_dims lowers to per-period
    custom_vjp boundaries whose compiled backward leg shows EXACTLY the
    planned all-to-alls (``expected_bwd_collectives``): steady-state
    periodic leg inside the while body, seam + carry-init + input-grad
    entry outside it — for whichever of the two loop-carry layouts XLA
    picks (jax 0.9.0 holds the ``swapped`` carry in ``bwd[-1]``, which
    drops the carry-init and the input-grad reshard).  The mirrored case
    is the control."""
    for name, case in hlo_counts["synthetic"].items():
        assert _a2a(case["fwd"]) == _a2a(case["planned_fwd"]), (name, case)
        bwd = _a2a(case["grad"]) - _a2a(case["fwd"])
        assert bwd in (_a2a(case["planned_bwd"]),
                       _a2a(case["planned_bwd_last"])), (name, case)
    # the contract distinguishes the legs: the forced plans' backward legs
    # differ from the mirrored control's
    syn = hlo_counts["synthetic"]
    assert _a2a(syn["swapped"]["planned_bwd"]) != \
        _a2a(syn["mirrored"]["planned_bwd"])


# ---------------------------------------------------------------------------
# Comm-compute overlap contract (PR 6)
# ---------------------------------------------------------------------------

def test_overlap_lowers_switches_to_permute_hops(hlo_counts):
    """Under overlap mode every planned switch decomposes into exactly n-1
    collective-permute hops and NO bare all-to-all survives — both modes."""
    ov = hlo_counts["overlap"]
    want = (ov["n_shards"] - 1) * ov["planned_switches"]
    for mode in ("chunked", "double_buffer"):
        c = ov[mode]["counts"]
        assert c.get("all-to-all", 0) == 0, (mode, c)
        assert c.get("collective-permute", 0) == want, (mode, c, want)
        assert c.get("all-gather", 0) == 0, (mode, c)


def test_overlap_permutes_span_kernel_compute(hlo_counts):
    """The hops are schedulable ACROSS the consuming kernel: no permute's
    operands reach another permute through data-movement ops alone — every
    permute->permute dependency path crosses kernel compute (fusion/dot).
    This is the structural spanning contract on a backend that lowers
    collectives synchronously; an async backend pipelines exactly these
    independent hops behind the kernel."""
    ov = hlo_counts["overlap"]
    for mode in ("chunked", "double_buffer"):
        assert ov[mode]["serialized_pairs"] == 0, (mode, ov[mode])


def test_overlap_parity_is_bitwise(hlo_counts):
    """Decomposed switches are numerically FREE: outputs bitwise equal to
    both the synchronous explicit executor and the auto path, gradients
    bitwise equal to the synchronous executor's."""
    ov = hlo_counts["overlap"]
    for mode in ("chunked", "double_buffer"):
        case = ov[mode]
        assert case["fwd_bitwise_vs_explicit"], (mode, case)
        assert case["fwd_bitwise_vs_auto"], (mode, case)
        assert case["grad_bitwise_vs_explicit"], (mode, case)


# ---------------------------------------------------------------------------
# Hybrid (ring x DSP) compiled contract (PR 7)
# ---------------------------------------------------------------------------

def test_hybrid_compiled_contract(hlo_counts):
    """On the ICI x DCN instance the strategy DP assigns hybrid to the
    temporal stages and the compiled forward shows EXACTLY the planned
    embedded collectives — 4 all-to-alls (q,k,v in + o out, inside ICI) and
    2*outer collective-permutes (the DCN ring) per hybrid stage, plus one
    all-to-all per planned switch (zero here: dims are constant) and
    NOTHING else.  No all-gather, no reduce-scatter: the hybrid never
    materializes an unsharded tensor."""
    hy = hlo_counts["hybrid"]
    assert hy["strategies"] == ["dsp", "hybrid"] * hy["n_periods"], hy
    # 2 hybrid stages x (4 a2a + 2*outer permutes), outer = 2
    assert hy["planned"] == {"all-to-all": 8, "collective-permute": 8}, hy
    assert hy["fwd"] == hy["planned"], hy


def test_scanned_lm_train_planned_backward_reaches_compiler(hlo_counts):
    """Scanned-LM train step: a forced non-mirrored joint plan leaves the
    forward leg untouched (identical collective counts) but changes the
    compiled backward — if ``require_mirrored=True`` came back (bwd_dims
    ignored), the two grad compiles would be identical and this fails."""
    lm = hlo_counts["lm_train"]
    assert lm["mirrored"]["mirrored"] and not lm["forced"]["mirrored"]
    assert lm["mirrored"]["fwd"] == lm["forced"]["fwd"], lm
    assert lm["mirrored"]["grad"] != lm["forced"]["grad"], lm
