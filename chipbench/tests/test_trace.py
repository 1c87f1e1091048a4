"""The reduction from trace events to busy time, idle gaps and spans."""
import gzip
import os

import numpy as np
import pytest

from chipbench import hlo, trace
from chipbench.trace import Event


def _ev(where, name, s, e):
    return Event(where, name, s * 1e6, e * 1e6)     # ms -> ns


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]


FUSION = ("%%fusion.%d = f32[8]{0:T(128)} fusion(f32[8]{0:T(128)} %%p), "
          "kind=kLoop, calls=%%fused_computation")
A2A = ("%all-to-all.3 = bf16[4,8]{1,0:T(8,128)(2,1)} "
       "all-to-all(bf16[4,8]{1,0:T(8,128)(2,1)} %x), replica_groups={{0,1}}")
WHILE = ("%while.9 = (s32[]{:T(128)}, f32[8]{0}) while((s32[]{:T(128)}, "
         "f32[8]{0}) %tuple.4), condition=%cond, body=%body")


def test_busy_idle_and_gaps_labelled_by_host_span():
    ev = [_ev("host", "data", 0, 1), _ev("host", "dispatch", 1, 2),
          _ev("host", "sync", 2, 10),
          _ev("host", "data", 10, 11), _ev("host", "dispatch", 11, 12),
          _ev("host", "sync", 12, 20),
          # chip 0: ops 1.5-9 (overlapping pair) and 12-19.5
          _ev("0", FUSION % 1, 1.5, 6), _ev("0", FUSION % 2, 5, 9),
          _ev("0", A2A, 12, 19.5),
          # control flow around the ops is not an op of its own
          _ev("0", WHILE, 0.5, 19.8),
          # chip 1: one op straddling the window's end
          _ev("1", FUSION % 1, 2, 25)]
    s = trace.reduce(ev, 2)
    assert s.window_s == pytest.approx(0.020)
    assert s.steps == 2
    # chip 0 busy 7.5 + 7.5 ms, chip 1 busy 18 ms (clipped at 20)
    assert s.busy_s == pytest.approx((0.015 + 0.018) / 2)
    assert s.idle_share == pytest.approx(1 - 0.0165 / 0.020)
    gaps = dict()
    for name, sec in s.gaps:
        gaps.setdefault(name, []).append(sec)
    # 0-1.5 (data 1 ms, dispatch 0.5 ms -> data), 9-12 (sync 1, data 1,
    # dispatch 1 -> the first that overlaps most), 19.5-20 (sync)
    assert sorted(s.gaps, key=lambda g: -g[1])[0][1] == pytest.approx(0.003)
    assert gaps["data"][0] == pytest.approx(0.0015)
    assert gaps["sync"][-1] == pytest.approx(0.0005)
    assert s.kind_seconds("all-to-all") == pytest.approx(0.0075 / 2)
    b = s.breakdown()
    assert b["device_ops"][0][0] == "fusion fusion.1"
    assert not any("while" in k for k, _ in b["device_ops"])
    assert len(b["idle_gaps"]) == 3


def test_a_chip_without_ops_is_an_error():
    ev = [_ev("host", "dispatch", 0, 1), _ev("0", FUSION % 1, 0, 1)]
    with pytest.raises(ValueError):
        trace.reduce(ev, 2)


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "t2d-720m.train.16x256.step.textproto.gz")


def test_recorded_chip_trace_of_one_720m_step():
    """One step of the 720M cell on a TPU v5e, recorded by ``--trace 1``
    and cut down by ``data/trim_trace.py``."""
    from jax.profiler import ProfileData
    with gzip.open(RECORDED, "rt") as f:
        profile = ProfileData.from_text_proto(f.read())
    events = trace.events_from_profile(profile, 1)
    s = trace.reduce(events, 1)
    assert s.steps == 1
    assert 0.2 < s.window_s < 0.4
    # busy time against a count of 1-us bins that any op covers
    lo = min(e.start_ns for e in events if e.where == "host")
    hi = lo + s.window_s * 1e9
    covered = np.zeros(int(s.window_s * 1e6) + 1, bool)
    for e in events:
        if e.where == "0" and e.end_ns > lo and e.start_ns < hi and \
                hlo.parse_event(e.name).opcode not in trace.CONTAINERS:
            a = int((max(e.start_ns, lo) - lo) / 1e3)
            b = int(np.ceil((min(e.end_ns, hi) - lo) / 1e3))
            covered[a:b] = True
    assert s.busy_s == pytest.approx(covered.sum() * 1e-6, rel=0.01)
    assert s.busy_s + sum(g for _, g in s.gaps) == pytest.approx(s.window_s)
    # 14 layer pairs x 2 blocks, each forward run again by remat
    pallas = s.of_kind("pallas")
    assert len(pallas) == 56
    assert {hlo.shape_of(ins.result)[1] for _, ins in pallas} == {
        (16, 16, 256, 72), (256, 16, 16, 72)}
    assert s.breakdown()["device_ops"][0][0].startswith("custom-call")


def test_readers_on_the_recorded_step():
    import types
    from jax.profiler import ProfileData
    from chipbench import flops
    from chipbench.harness import Benchmark
    from chipbench.reference import Model
    bench = Benchmark()
    cell = "t2d-720m.train.16x256"
    with gzip.open(RECORDED, "rt") as f:
        s = trace.reduce(trace.events_from_profile(
            ProfileData.from_text_proto(f.read()), 1), 1)
    config = bench.config("t2d-720m")
    m = types.SimpleNamespace(
        trace=s, config=config, traffic=bench.traffic("train.16x256"),
        chips=1, peaks=flops.peaks("TPU v5 lite"),
        model=Model.from_config(config), steps=s.steps)
    got = {x["name"]: bench.reader(x["name"]).read(m)
           for x in bench.metrics(cell, trace=True)}
    assert got["device_idle_pct"] == pytest.approx(100 * s.idle_share)
    # 11.18 TFLOP in one 0.288 s step of a 197 TFLOP/s chip
    assert got["step_mfu"] == pytest.approx(
        100 * 11.18e12 / (s.window_s * 197e12), rel=2e-3)
    assert 50 < got["flash_fwd_ms_per_step"] < 120
    assert 0 < got["flash_fwd_roofline"] < 100
