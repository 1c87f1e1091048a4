"""Paper Table 2 + Table 3: per-layer communication volume of each SP method
on the 2D transformer — analytic model AND measured from compiled HLO on a
simulated 8-device ring.

Table 3 claims (activation size M, N devices):
    DSP 2M/N | Ulysses 4M/N | Megatron-SP 8M | Ring 2M

All analytic numbers are priced with the SAME constant the planner and the
schedule executor use (``repro.core.dsp.comm_volume_bytes``: switch = M/N,
gather = M); for DSP the script additionally reports the PLANNED volume from
the model's own solved schedule (``transformer2d.dsp_schedule``) next to the
measured HLO bytes — planned-vs-measured is the executor's contract — and
the planned training ROUND TRIP: forward and backward legs priced separately
(the backward is planned by the joint DP, not assumed to mirror the
forward; see docs/architecture.md §2.4).

Since PR 5 the scanned LM/enc-dec executors RUN non-mirrored joint plans
(per-period custom_vjp boundaries), so the script also reports the
EXECUTED scanned round trip — the joint schedule the scanned-LM train step
compiles, priced per leg on the flat-ICI and ICIxDCN fabrics, with the
executed per-leg collective counts from the executor's own accounting.

PR 6 adds the comm-compute overlap row: the scanned dsp forward with every
planned switch decomposed into per-shard collective-permute hops
(``core.overlap.overlapped_switch``), wall-clocked against the synchronous
executor on the 8-device sim, with the planner's exposed/hidden seconds
split per fabric and a ``notes`` field explaining the result.

Everything lands in ``BENCH_comm.json`` at the repo root (planned vs
measured bytes/seconds per mode and fabric) so the trajectory is tracked
across PRs; CI smokes the schema with ``--quick`` (dsp-only measurement +
the overlap row).
"""
import argparse
import json
import os
import sys

_ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)

from benchmarks.common import spmd_measure, emit
from repro.core.dsp import per_device_bytes

N = 8
LAYERS = 4          # 2 layer-pairs
MODES = ["dsp", "ulysses", "ulysses_fused", "ring", "megatron"]

# benchmark mode -> strategy constant (core.topology.STRATEGIES); the fused
# ulysses variant moves the same bytes in half the launches
_STRATEGY_OF_MODE = {"dsp": "dsp", "ulysses": "ulysses",
                     "ulysses_fused": "ulysses", "ring": "ring",
                     "megatron": "megatron", "hybrid": "hybrid"}


def analytic_bytes(mode: str, m_bytes: float, n: int, *, kv_bytes=None,
                   kv_heads=None, outer=1) -> float:
    """Per-layer analytic volume, routed through the ONE shared constant
    (``core.dsp.per_device_bytes``) that the strategy DP and the mode
    implementations (``core.ulysses.attention_bytes``,
    ``core.ring.stream_bytes``, ``core.megatron_sp.block_bytes``) also
    price from.  ``per_device_bytes`` is per STAGE; a 2D-transformer layer
    runs megatron's AG/RS wrapping in BOTH blocks (x2 = Table 3's 8M),
    every other mode pays its collectives once per layer."""
    v = per_device_bytes(_STRATEGY_OF_MODE[mode], m_bytes, n,
                         kv_bytes=kv_bytes, kv_heads=kv_heads, outer=outer)
    return 2 * v if mode == "megatron" else v


def _fabrics():
    from repro.core.topology import Topology
    return (("ici", Topology.flat_ici(N)),
            ("ici_dcn", Topology.multihost(2, N // 2)))


def _leg_seconds(sched) -> dict:
    out = {}
    for label, topo in _fabrics():
        rs = sched.roundtrip_seconds(topo)
        out[label] = {"fwd_seconds": rs.fwd, "bwd_seconds": rs.bwd,
                      "roundtrip_seconds": rs.total,
                      "bottleneck_gbps": topo.bottleneck_bandwidth / 1e9}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="measure only the dsp mode (CI schema smoke)")
    ap.add_argument("--out", default=os.path.join(_ROOT, "BENCH_comm.json"))
    args = ap.parse_args(argv)

    b, t, s, d = 2, 16, 32, 128
    m_bytes = b * t * s * d * 4          # f32 activation size
    pairs = LAYERS // 2
    record = {"config": {"devices": N, "layers": LAYERS, "batch": b,
                         "temporal": t, "spatial": s, "d_model": d},
              "modes": {}}
    rows = {}
    modes = ["dsp"] if args.quick else MODES
    for mode in modes:
        r = spmd_measure(N, mode, batch=b, temporal=t, spatial=s,
                         layers=LAYERS, d_model=d, modulate=False)
        per_layer = r["collective_bytes_per_dev"] / pairs
        rows[mode] = per_layer
        pred = analytic_bytes(mode, m_bytes, N)
        record["modes"][mode] = {
            "measured_bytes_per_layer": per_layer,
            "analytic_bytes_per_layer": pred,
            "ratio": per_layer / max(pred, 1),
            "counts": r["by_kind_count"],
        }
        emit(f"table3/comm_volume/{mode}", None,
             f"measured_bytes_per_layer={per_layer:.0f};"
             f"analytic={pred:.0f};ratio={per_layer/max(pred, 1):.2f};"
             f"counts={r['by_kind_count']}")

    # planned-vs-measured for DSP: the model's own solved schedule must
    # price what the compiled HLO actually moves
    from repro.models.transformer2d import T2DConfig, dsp_schedule
    import jax.numpy as jnp
    cfg = T2DConfig(name="bench", n_layers=LAYERS, d_model=d, n_heads=8,
                    d_ff=256, in_dim=16, modulate=False, dtype=jnp.float32)
    psched = dsp_schedule(cfg, N, t_len=t, s_len=s, batch=b)
    planned_total = psched.schedule.per_device_bytes(N)
    measured_total = rows["dsp"] * pairs
    emit("table3/planned_vs_measured/dsp", None,
         f"planned_bytes={planned_total:.0f};measured={measured_total:.0f};"
         f"ratio={measured_total/max(planned_total, 1):.2f};"
         f"planned_switches={psched.schedule.n_switches()}")

    # planned SECONDS next to planned bytes: the same schedule priced on two
    # modeled fabrics (flat ICI ring vs the SP group spanning 2 hosts over
    # DCN) — bytes are identical, time is not, which is exactly why the
    # planner optimises seconds on a Topology
    for label, topo in _fabrics():
        secs = psched.schedule.per_device_seconds(topo)
        emit(f"table3/planned_seconds/{label}", None,
             f"planned_bytes={planned_total:.0f};"
             f"planned_seconds={secs:.3e};"
             f"bottleneck_gbps={topo.bottleneck_bandwidth/1e9:.1f}")

    # the ROUND TRIP: training pays the backward's collectives too.  The
    # joint fwd+bwd planner (core.plan.plan_joint) prices the backward as
    # its own stage graph; on this symmetric model the mirrored plan is
    # optimal (bwd == fwd volumes) and the planner must keep it.
    jsched = dsp_schedule(cfg, N, t_len=t, s_len=s, batch=b,
                          joint=True).schedule
    rb = jsched.roundtrip_bytes(N)
    emit("table3/planned_roundtrip/bytes", None,
         f"fwd_bytes={rb.fwd:.0f};bwd_bytes={rb.bwd:.0f};"
         f"total={rb.total:.0f};bwd_mirrored={jsched.mirrored}")
    assert jsched.mirrored and rb.bwd == rb.fwd
    t2d_fabrics = _leg_seconds(jsched)
    for label, legs in t2d_fabrics.items():
        emit(f"table3/planned_roundtrip/{label}", None,
             f"fwd_seconds={legs['fwd_seconds']:.3e};"
             f"bwd_seconds={legs['bwd_seconds']:.3e};"
             f"roundtrip_seconds={legs['roundtrip_seconds']:.3e}")
    record["dsp"] = {
        "planned_bytes": planned_total,
        "measured_bytes": measured_total,
        "planned_switches": psched.schedule.n_switches(),
        "roundtrip": {"fwd_bytes": rb.fwd, "bwd_bytes": rb.bwd,
                      "total_bytes": rb.total,
                      "bwd_mirrored": jsched.mirrored},
        "fabrics": t2d_fabrics,
    }

    # the EXECUTED scanned round trip (PR 5): the joint schedule the
    # scanned-LM train step actually compiles — the scanned executors run
    # non-mirrored plans through per-period custom_vjp boundaries, so the
    # schedule priced below IS the schedule the train step executes (one
    # object; identity pinned by tests/test_hlo_collectives.py).  The
    # per-leg collective counts are the executor-structure ACCOUNTING
    # (exact for the executor path — t2d/synthetic scan — by the HLO tier;
    # the LM's hook path lowers the fused QKV switch as multiple smaller
    # all-to-alls, so its instruction counts differ even though the moved
    # bytes match), reported on both fabrics
    from repro.core.layout import from_mesh
    from repro.launch.mesh import make_mesh
    from repro.core.schedule import ScheduleExecutor
    from repro.models.lm import (LMConfig, dsp_schedule as lm_schedule,
                                 stage_period)
    lcfg = LMConfig(name="bench", n_layers=LAYERS, d_model=d, n_heads=8,
                    n_kv_heads=8, head_dim=d // 8, d_ff=2 * d, vocab=256,
                    dtype=jnp.float32)
    lsched = lm_schedule(lcfg, N, seq=t * s, batch=b, joint=True)
    lrb = lsched.roundtrip_bytes(N)
    ex = ScheduleExecutor(lsched.periodic(stage_period(lcfg)),
                          backend="auto",
                          ctx=from_mesh(make_mesh((1, 1),
                                                  ("data", "model"))))
    lm_fabrics = _leg_seconds(lsched)
    record["scanned_lm"] = {
        "planned_fwd_bytes": lrb.fwd,
        "planned_bwd_bytes": lrb.bwd,
        "bwd_mirrored": lsched.mirrored,
        "executed_bwd_dims_period": list(
            lsched.bwd_plan[:stage_period(lcfg)]),
        "accounted_fwd_collectives": ex.expected_collectives(lcfg.n_layers),
        "accounted_bwd_collectives": ex.expected_bwd_collectives(
            lcfg.n_layers),
        "fabrics": lm_fabrics,
    }
    for label, legs in lm_fabrics.items():
        emit(f"table3/scanned_roundtrip/{label}", None,
             f"fwd_seconds={legs['fwd_seconds']:.3e};"
             f"bwd_seconds={legs['bwd_seconds']:.3e};"
             f"roundtrip_seconds={legs['roundtrip_seconds']:.3e};"
             f"bwd_mirrored={lsched.mirrored}")

    # comm-compute OVERLAP (PR 6): the same scanned dsp forward with every
    # planned switch decomposed into n-1 collective-permute hops
    # (core.overlap.overlapped_switch), wall-clocked against the
    # synchronous executor on the 8-device sim, next to the planned
    # exposed/hidden split per fabric from the overlap-aware schedule.
    # Included in --quick so CI smokes the schema row.
    r_sync = spmd_measure(N, "dsp", batch=b, temporal=t, spatial=s,
                          layers=LAYERS, d_model=d, modulate=False,
                          time_it=True, reps=10)
    r_ov = spmd_measure(N, "dsp", batch=b, temporal=t, spatial=s,
                        layers=LAYERS, d_model=d, modulate=False,
                        time_it=True, reps=10, overlap="chunked")
    speedup = r_sync["us_per_call"] / max(r_ov["us_per_call"], 1e-9)
    overlap_fabrics = {}
    for label, topo in _fabrics():
        so = dsp_schedule(cfg, N, t_len=t, s_len=s, batch=b, topology=topo,
                          overlap="chunked").schedule
        overlap_fabrics[label] = {
            "planned_sync_seconds": so.per_device_seconds(topo),
            "planned_exposed_seconds": so.exposed_seconds(),
            "planned_hidden_seconds": so.hidden_comm_seconds(),
        }
        emit(f"table3/overlap/{label}", None,
             f"planned_sync_seconds="
             f"{overlap_fabrics[label]['planned_sync_seconds']:.3e};"
             f"exposed={overlap_fabrics[label]['planned_exposed_seconds']:.3e};"
             f"hidden={overlap_fabrics[label]['planned_hidden_seconds']:.3e}")
    if speedup >= 1.0:
        notes = (f"overlapped executor beats synchronous by "
                 f"{(speedup - 1) * 100:.1f}% wall-clock on the 8-device "
                 f"CPU sim")
    else:
        notes = (f"overlapped executor {1/max(speedup, 1e-9):.2f}x slower "
                 "wall-clock on this 8-device SIM: XLA:CPU lowers "
                 "collective-permute synchronously (no -start/-done "
                 "pipelining) and all 8 'devices' share one socket, so the "
                 "decomposition pays n-1 launch overheads and hides "
                 "nothing; the contract that the hops are independent and "
                 "SPAN the kernel (so an async backend pipelines them) is "
                 "pinned structurally in tests/test_hlo_collectives.py, "
                 "and the planned hidden seconds above quantify the win on "
                 "a modeled fabric")
    record["overlap"] = {
        "mode": "chunked",
        "sync_us_per_call": r_sync["us_per_call"],
        "overlap_us_per_call": r_ov["us_per_call"],
        "speedup": speedup,
        "counts": r_ov["by_kind_count"],
        "fabrics": overlap_fabrics,
        "notes": notes,
    }
    emit("table3/overlap/walltime", r_ov["us_per_call"],
         f"sync_us={r_sync['us_per_call']:.0f};"
         f"overlap_us={r_ov['us_per_call']:.0f};speedup={speedup:.2f};"
         f"counts={r_ov['by_kind_count']}")

    # megatron-SP planned SECONDS per fabric: it was the only mode reported
    # in bytes but never in Topology-priced time.  One t2d layer wraps both
    # blocks, each with an attention AND an MLP AG/RS pair = 4x
    # core.megatron_sp.block_seconds (alpha+beta ag + rs of the full M)
    from repro.core.megatron_sp import block_seconds
    meg_fabrics = {}
    for label, topo in _fabrics():
        meg_fabrics[label] = {
            "planned_seconds_per_layer": 4 * block_seconds(topo, m_bytes)}
        emit(f"table3/megatron_planned_seconds/{label}", None,
             f"planned_seconds_per_layer="
             f"{meg_fabrics[label]['planned_seconds_per_layer']:.3e}")
    record["megatron_sp"] = {
        "analytic_bytes_per_layer": analytic_bytes("megatron", m_bytes, N),
        "fabrics": meg_fabrics,
    }

    # ---- first-class 2D layouts row (TSP fold) ----------------------------
    # Two pinned facts about planning over dim PAIRS on the (2, 4) sp2d
    # grid.  (1) CONSERVATIVE: the 2D layout space contains the 1D plans as
    # its diagonal, so with the same entry/exit pinning the 2D DP is never
    # worse than the 1D DP on the same fabric — on this symmetric bench
    # instance it lands exactly on the embedded 1D plan (a joint a2a moves
    # M/N once; two per-axis a2as would move it twice, and the planner
    # knows it).  (2) ENABLING: on the TSP-fold instance (T=4, S=12,
    # 4 heads) NO dim extent divides the 8-way SP degree, so the 1D space
    # cannot shard the model at all (XLA would pad + involuntarily remat)
    # — dim-pair layouts split the factor across two dims and restore full
    # 8-way sharding, with the compiled forward2d HLO pinned to the
    # executor's per-axis accounting.  Runs under --quick.
    from repro.core.plan import (layout_allows, plan_cost_seconds,
                                 plan2d_cost_seconds, plan_switches_2d,
                                 plan_switches_dp)
    from repro.core.schedule import ScheduleExecutor2D
    from repro.launch.mesh import sp2d_topology
    from repro.models.transformer2d import dsp2d_schedule, stages2d
    grid2d = (2, N // 2)
    topo2d = sp2d_topology(*grid2d)          # == Topology.multihost(2, 4)
    bench_st = stages2d(cfg, t_len=t, s_len=s, batch=b)
    plan_1d = plan_switches_dp(bench_st, [1, 2, 3], n=N, initial=1, final=1,
                               topology=topo2d)
    secs_1d = plan_cost_seconds(bench_st, plan_1d, topo2d, initial=1,
                                final=1)
    plan_2d = plan_switches_2d(bench_st, [1, 2, 3], grid=grid2d, initial=1,
                               final=1, topology=topo2d)
    secs_2d = plan2d_cost_seconds(bench_st, plan_2d, topo2d, initial=1,
                                  final=1)
    assert secs_2d <= secs_1d, (
        f"2D plan space contains the 1D diagonal but planned worse: "
        f"{secs_2d:.3e}s > {secs_1d:.3e}s")

    fcfg = T2DConfig(name="fold", n_layers=LAYERS, d_model=d, n_heads=4,
                     d_ff=256, in_dim=16, modulate=False, dtype=jnp.float32)
    fb, ft, fs = 2, 4, 12
    fold_st = stages2d(fcfg, t_len=ft, s_len=fs, batch=fb)
    assert not any(layout_allows(stg, (dim, dim), grid2d)
                   for stg in fold_st for dim in (1, 2, 3)), (
        "fold instance must be unshardable in the 1D (diagonal) space")
    p2 = dsp2d_schedule(fcfg, grid2d, t_len=ft, s_len=fs, batch=fb,
                        topology=topo2d)
    ex2d = ScheduleExecutor2D(p2, backend="null")
    expected2d = ex2d.expected_carry_collectives(pairs)
    r2d = spmd_measure(N, "layout2d", batch=fb, temporal=ft, spatial=fs,
                       layers=LAYERS, d_model=d, heads=4, modulate=False,
                       sp_outer=grid2d[0])
    assert {k: int(v) for k, v in r2d["by_kind_count"].items()
            if v} == expected2d, (r2d["by_kind_count"], expected2d)
    record["layout2d"] = {
        "grid": list(grid2d),
        "bench_planned_seconds": {"plan_1d": secs_1d, "plan_2d": secs_2d},
        "fold_config": {"batch": fb, "temporal": ft, "spatial": fs,
                        "d_model": d, "n_heads": 4},
        "fold_layouts_per_period": [list(lo) for lo in p2.layouts],
        "fold_planned_bytes": p2.schedule.per_device_bytes(),
        "fold_planned_seconds_ici_dcn": p2.schedule.per_device_seconds(),
        "fold_measured_bytes": r2d["collective_bytes_per_dev"],
        "counts": r2d["by_kind_count"],
        "expected_counts": expected2d,
    }
    emit("table3/layout2d/conservative", None,
         f"planned_seconds_1d={secs_1d:.3e};planned_seconds_2d={secs_2d:.3e}"
         f";embedded_diagonal={all(lo[0] == lo[1] for lo in plan_2d)}")
    emit("table3/layout2d/fold", None,
         f"planned_bytes={p2.schedule.per_device_bytes():.0f};"
         f"measured={r2d['collective_bytes_per_dev']:.0f};"
         f"counts={r2d['by_kind_count']};"
         f"layouts={[list(lo) for lo in p2.layouts]}")

    # ---- unified-plan HYBRID row (the (stage, dim, strategy) DP) ----------
    # Instance: long-temporal latents (T=128, S=4) with GQA (8 q heads, 4 kv
    # heads) on the ICI x DCN fabric.  S=4 divides the per-host ICI group
    # but NOT the 8-way SP axis, so dim 2's shard can only live inside a
    # host (placement={2: ("ici",)} is forced) — pure DSP's alternation
    # pays a cross-placement switch + DCN gather per pair, while the DP's
    # hybrid pick stays resident on T and runs USP at temporal stages: a2a
    # q/k/v inside ICI, K/V ring across DCN.  kv_heads=4 also handicaps
    # pure Ulysses (4 % 8 != 0 -> K/V replication).  Runs under --quick so
    # CI smokes the row.
    from repro.models.transformer2d import (strategy_schedule,
                                            stages as t2d_stages)
    from repro.core.topology import Topology
    from repro.core.plan import (StrategyPlan, plan_switches_dp,
                                 strategy_plan_cost)
    hb, ht, hs, hd = 2, 128, 4, 128
    h_outer = 2
    hcfg = T2DConfig(name="hybrid", n_layers=LAYERS, d_model=hd, n_heads=8,
                     d_ff=256, in_dim=16, modulate=False, n_kv_heads=4,
                     dtype=jnp.float32)
    hm_bytes = hb * ht * hs * hd * 4
    hkv_bytes = 2.0 * hb * ht * hs * hcfg.kvh * hcfg.dh * 4
    topo_h = Topology.multihost(2, N // 2, placement={2: ("ici",)})
    hsched = strategy_schedule(hcfg, N, t_len=ht, s_len=hs, batch=hb,
                               topology=topo_h, initial=1)
    hstages = t2d_stages(hcfg, t_len=ht, s_len=hs, batch=hb)
    hybrid_planned = hsched.schedule.strategy_seconds() / pairs

    # every PURE mode on the same instance/fabric, priced by the same
    # strategy cost model: dsp = the classic switch DP's plan; the embedded
    # modes stay resident on T and run their strategy at temporal stages
    pure = {}
    dsp_dims = plan_switches_dp(hstages, [1, 2], topology=topo_h,
                                initial=1, final=1)
    pure["dsp"] = strategy_plan_cost(
        hstages, StrategyPlan(tuple(dsp_dims), ("dsp",) * LAYERS),
        topology=topo_h, initial=1, final=1) / pairs
    for strat in ("ulysses", "ring", "megatron"):
        plan = StrategyPlan((1,) * LAYERS, ("dsp", strat) * pairs)
        pure[strat] = strategy_plan_cost(hstages, plan, topology=topo_h,
                                         initial=1, final=1) / pairs
    assert all(hybrid_planned < v for v in pure.values()), (
        f"hybrid planned {hybrid_planned} not strictly cheaper than every "
        f"pure mode: {pure}")

    rh = spmd_measure(N, "hybrid", batch=hb, temporal=ht, spatial=hs,
                      layers=LAYERS, d_model=hd, modulate=False,
                      n_kv_heads=hcfg.kvh, sp_outer=h_outer)
    h_per_pair = rh["collective_bytes_per_dev"] / pairs
    h_analytic = analytic_bytes("hybrid", hm_bytes, N, kv_bytes=hkv_bytes,
                                kv_heads=hcfg.kvh, outer=h_outer)
    record["hybrid"] = {
        "config": {"devices": N, "layers": LAYERS, "batch": hb,
                   "temporal": ht, "spatial": hs, "d_model": hd,
                   "n_heads": hcfg.n_heads, "n_kv_heads": hcfg.kvh,
                   "sp_outer": h_outer, "fabric": "ici_dcn",
                   "placement": {"2": ["ici"]}},
        "strategies_per_period": list(hsched.strategies),
        "dims_per_period": list(hsched.dims),
        "planned_seconds_per_pair": hybrid_planned,
        "pure_planned_seconds_per_pair": pure,
        "measured_bytes_per_pair": h_per_pair,
        "analytic_bytes_per_pair": h_analytic,
        "ratio": h_per_pair / max(h_analytic, 1),
        "counts": rh["by_kind_count"],
    }
    emit("table3/hybrid/planned_seconds", None,
         f"hybrid={hybrid_planned:.3e};"
         + ";".join(f"{k}={v:.3e}" for k, v in pure.items())
         + f";strategies={list(hsched.strategies)}")
    emit("table3/hybrid/bytes", None,
         f"measured_per_pair={h_per_pair:.0f};analytic={h_analytic:.0f};"
         f"ratio={h_per_pair/max(h_analytic, 1):.2f};"
         f"counts={rh['by_kind_count']}")

    if not args.quick:
        # the paper's headline ordering must hold in the measured HLO
        assert rows["dsp"] < rows["ulysses"] < rows["megatron"]
        assert rows["dsp"] < rows["ring"]
        emit("table3/ordering", None,
             f"dsp<ulysses<megatron and dsp<ring confirmed;"
             f"dsp_vs_ulysses_reduction={1 - rows['dsp']/rows['ulysses']:.2%}")

    with open(args.out, "w") as fh:
        json.dump(record, fh, indent=1)
    emit("table3/json", None, f"wrote {args.out}")


if __name__ == "__main__":
    main()
