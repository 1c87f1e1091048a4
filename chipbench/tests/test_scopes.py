"""Device time by the program's scope names (``chipbench.scopes``): the
reading of a trace, its trimming into test data, and the per-layer readers
on a recorded chip trace."""
import os
import types

import pytest

from chipbench import scopes
from chipbench.harness import Benchmark

STEP = "jit(step)/jvp()/while/body/closed_call/"
BWD = "jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
RECOMPUTE = BWD + "rematted_computation/"
# (HLO text, op_name, start ms, end ms) of one synthetic step
OPS = [
    ("%custom-call.1 = bf16[256,16,128,72]{3,2,1,0} custom-call(...), "
     'custom_call_target="tpu_custom_call"',
     STEP + "temporal/attn/flash_fwd", 1.0, 3.0),
    ("%custom-call.2 = bf16[256,16,128,72]{3,2,1,0} custom-call(...), "
     'custom_call_target="tpu_custom_call"',
     RECOMPUTE + "temporal/attn/flash_fwd", 3.0, 5.0),
    ("%fusion.3 = bf16[16,16,256,72]{3,2,1,0} fusion(...), calls=%f3",
     STEP + "spatial/attn/transpose", 5.0, 5.5),
    ("%fusion.4 = f32[16,16,256,256]{3,2,1,0} fusion(...), calls=%f4",
     BWD + "spatial/attn/attn_bwd/transpose(jvp())/dot_general", 5.5, 7.0),
    ("%fusion.5 = bf16[4096,4608]{1,0} fusion(...), calls=%f5",
     STEP + "spatial/mlp/dot_general", 7.0, 8.0),
    ("%fusion.6 = bf16[4096,4608]{1,0} fusion(...), calls=%f6",
     BWD + "spatial/mlp/dot_general", 8.0, 9.5),
    ("%fusion.7 = f32[1152,6912]{1,0} fusion(...), calls=%f7",
     "jit(step)/adamw/sub", 9.5, 10.0),
    ("%copy.8 = f32[8]{0} copy(...)", "", 10.0, 10.5),
]


def _synthetic(tmp_path, named=True):
    """A binary trace of two steps on one chip, each with ``OPS``, whose
    ops carry their op_name as the chip's trace does (``tf_op``)."""
    space = scopes._schema()()
    host = space.planes.add(id=1, name="/host:CPU")
    host_line = host.lines.add(id=1, name="python", timestamp_ns=0)
    dev = space.planes.add(id=2, name="/device:TPU:0")
    dev_line = dev.lines.add(id=1, name="XLA Ops", timestamp_ns=0)
    dev.stat_metadata[7].id, dev.stat_metadata[7].name = 7, "tf_op"
    for i, (text, op_name, _, _) in enumerate(OPS, 1):
        md = dev.event_metadata[i]
        md.id, md.name = i, text
        if named and op_name:
            md.stats.add(metadata_id=7, str_value=op_name + ":")
    for k, name in enumerate(("data", "dispatch", "sync"), 1):
        host.event_metadata[k].id, host.event_metadata[k].name = k, name
    for step in range(2):
        t0 = step * 12.0
        for k, (s, e) in enumerate(((0, 0.5), (0.5, 1.0), (1.0, 12.0)), 1):
            host_line.events.add(metadata_id=k, offset_ps=int((t0 + s) * 1e9),
                                 duration_ps=int((e - s) * 1e9))
        for i, (_, _, s, e) in enumerate(OPS, 1):
            dev_line.events.add(metadata_id=i,
                                offset_ps=int((t0 + s) * 1e9),
                                duration_ps=int((e - s) * 1e9))
    path = str(tmp_path / "synthetic.xplane.pb")
    with open(path, "wb") as f:
        f.write(space.SerializeToString())
    return path


def _readings(monkeypatch, path, steps):
    monkeypatch.setattr(scopes, "trace_file", lambda: path)
    m = types.SimpleNamespace(chips=1, steps=steps)
    bench = Benchmark()
    return {x["name"]: bench.reader(x["name"]).read(m)
            for x in bench.spec["per_layer"]
            if x["name"] in READERS}


READERS = ("attn_fwd_temporal_ms_per_step", "attn_fwd_spatial_ms_per_step",
           "attn_bwd_ms_per_step", "mlp_ms_per_step", "adamw_ms_per_step")


def test_ops_carry_their_scopes_and_legs(tmp_path):
    found = scopes.ops(_synthetic(tmp_path), 1)
    assert len(found) == 2 * len(OPS)
    first = found[:len(OPS)]
    assert first[0].scopes == {"temporal", "attn"} and not first[0].backward
    # remat's recompute counts with the forward
    assert not first[1].backward
    assert first[3].scopes == {"spatial", "attn", "attn_bwd"}
    assert first[3].backward and first[5].backward
    assert first[6].scopes == {"adamw"}
    assert first[7].scopes == frozenset()
    # 9 of the 9.5 busy ms of a step carry a scope
    assert scopes.scoped_share(found) == pytest.approx(9 / 9.5)


def test_readers_sum_their_scopes_per_step(tmp_path, monkeypatch):
    got = _readings(monkeypatch, _synthetic(tmp_path), steps=2)
    assert got == pytest.approx({
        "attn_fwd_temporal_ms_per_step": 4.0,
        "attn_fwd_spatial_ms_per_step": 0.5,
        "attn_bwd_ms_per_step": 1.5,
        "mlp_ms_per_step": 2.5,
        "adamw_ms_per_step": 0.5})


def test_readers_give_nothing_without_scope_names(tmp_path, monkeypatch):
    """A program that names no scope, as the parent of the names did."""
    got = _readings(monkeypatch, _synthetic(tmp_path, named=False), steps=2)
    assert got == dict.fromkeys(READERS)
    monkeypatch.setattr(scopes, "trace_file", lambda: None)
    m = types.SimpleNamespace(chips=1, steps=2)
    assert scopes.ms_per_step(m, lambda op: True) is None


def test_trim_keeps_each_ops_scope(tmp_path):
    path = _synthetic(tmp_path)
    out = str(tmp_path / "cut.textproto.gz")
    scopes.trim(path, out, steps=1)
    whole, cut = scopes.ops(path, 1), scopes.ops(out, 1)
    assert len(cut) == len(OPS)
    assert [(op.scopes, op.backward, op.instr, op.seconds) for op in cut] == \
        [(op.scopes, op.backward, op.instr, op.seconds)
         for op in whole[:len(OPS)]]


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "t2d-720m.train.16x256.scopes.textproto.gz")


@pytest.fixture(scope="module")
def recorded():
    """One step of the 720M cell on a TPU v5e, recorded by ``--trace 1``
    and cut down by ``scopes.trim``: its ops, and the reduction and
    metric input the harness gives the readers."""
    import gzip
    from jax.profiler import ProfileData
    from chipbench import flops, trace
    from chipbench.reference import Model
    with gzip.open(RECORDED, "rt") as f:
        summary = trace.reduce(trace.events_from_profile(
            ProfileData.from_text_proto(f.read()), 1), 1)
    bench = Benchmark()
    config = bench.config("t2d-720m")
    m = types.SimpleNamespace(
        trace=summary, config=config, traffic=bench.traffic("train.16x256"),
        chips=1, peaks=flops.peaks("TPU v5 lite"),
        model=Model.from_config(config), steps=summary.steps)
    return scopes.ops(RECORDED, 1), m


def test_readers_on_the_recorded_step(recorded, monkeypatch):
    _, m = recorded
    assert m.steps == 1
    monkeypatch.setattr(scopes, "trace_file", lambda: RECORDED)
    bench = Benchmark()
    got = {name: bench.reader(name).read(m) for name in READERS}
    assert all(isinstance(v, float) and v > 0 for v in got.values()), got


def test_the_recorded_step_is_named_by_its_scopes(recorded):
    found, m = recorded
    assert scopes.scoped_share(found) >= 0.95
    # the harness's reduction's busy time, to ProfileData's truncation of
    # each event to whole nanoseconds
    assert sum(e - s for s, e in scopes.trace.union(
        [(op.start_ns, op.end_ns) for op in found])) * 1e-9 == \
        pytest.approx(m.trace.busy_s, rel=1e-4)


def test_pallas_ops_under_attn_are_the_flash_forward(recorded):
    found, m = recorded
    flash = Benchmark().reader("flash_fwd_ms_per_step").read(m)
    attn = 1e3 * sum(op.seconds for op in found
                     if scopes.hlo.event_kind(op.instr) == "pallas"
                     and "attn" in op.scopes) / m.steps
    assert attn == pytest.approx(flash, rel=0.01)
