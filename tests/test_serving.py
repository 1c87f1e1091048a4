"""Plan-aware ServingEngine tests: per-request decode budgets + EOS masking,
the continuous-batching scheduler (parity oracle vs static ``generate``,
slot reuse, admission budget, streaming, arrivals) — single device,
in-process — and the elastic re-plan path (8 simulated devices, fresh
subprocess — same pattern as tests/test_multidevice.py)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.lm import LMConfig, init_lm
from repro.serving.engine import Request, RequestResult, ServingEngine
from repro.serving.kv_pool import KVPool, PoolExhausted
from repro.serving.scheduler import ContinuousScheduler, replay_static

HERE = os.path.dirname(__file__)
SRC = os.path.join(HERE, "..", "src")

TINY = LMConfig(name="tiny-serve", n_layers=2, d_model=32, n_heads=4,
                n_kv_heads=2, head_dim=8, d_ff=64, vocab=64,
                dtype=jnp.float32)


@pytest.fixture(scope="module")
def engine():
    params = init_lm(jax.random.PRNGKey(0), TINY)
    return ServingEngine(params, TINY, max_len=32)


@pytest.fixture(scope="module")
def prompts():
    return jax.random.randint(jax.random.PRNGKey(1), (3, 8), 0, TINY.vocab)


def test_scalar_budget_unchanged(engine, prompts):
    """Scalar max_new_tokens + no EOS reproduces the original static loop."""
    out = np.asarray(engine.generate(prompts, max_new_tokens=6))
    assert out.shape == (3, 6)
    # greedy decode is deterministic: a second run is identical
    assert np.array_equal(out,
                          np.asarray(engine.generate(prompts, 6)))


def test_per_request_budgets_masked(engine, prompts):
    ref = np.asarray(engine.generate(prompts, 8))
    out = np.asarray(engine.generate(prompts, [8, 3, 1], pad_id=-1))
    assert out.shape == (3, 8)                      # max budget sets width
    assert np.array_equal(out[0], ref[0])           # full row untouched
    assert np.array_equal(out[1, :3], ref[1, :3])   # budget-3 row: 3 real...
    assert (out[1, 3:] == -1).all()                 # ...then pad
    assert np.array_equal(out[2, :1], ref[2, :1])
    assert (out[2, 1:] == -1).all()
    with pytest.raises(ValueError):
        engine.generate(prompts, [8, 3])            # wrong budget count
    with pytest.raises(ValueError):
        engine.generate(prompts, 0)                 # budgets must be >= 1
    with pytest.raises(ValueError):
        engine.generate(prompts, 64)                # exceeds max_len


def test_eos_early_exit(engine, prompts):
    ref = np.asarray(engine.generate(prompts, 8))
    eos = int(ref[0, 2])                    # force an EOS hit at step 2
    out = np.asarray(engine.generate(prompts, 8, eos_id=eos, pad_id=-1))
    for b in range(out.shape[0]):
        row, rref = out[b], ref[b]
        if (rref == eos).any():
            k = int(np.argmax(rref == eos))
            assert np.array_equal(row[:k + 1], rref[:k + 1])  # incl. the EOS
            assert (row[k + 1:] == -1).all()                  # then pad
        else:
            assert np.array_equal(row, rref)
    # all rows finishing early must not change emitted prefixes (the loop
    # early-exits but outputs are already masked)
    out1 = np.asarray(engine.generate(prompts, [1, 1, 1], eos_id=eos))
    assert np.array_equal(out1[:, 0], ref[:, 0])


def test_serve_requests_roundtrip(engine, prompts):
    ref = np.asarray(engine.generate(prompts, 8))
    reqs = [Request(prompt=prompts[i], max_new_tokens=m)
            for i, m in enumerate((8, 3, 5))]
    engine.serve(reqs)
    assert reqs[0].generated == ref[0].tolist()
    assert reqs[1].generated == ref[1, :3].tolist()
    assert reqs[2].generated == ref[2, :5].tolist()
    with pytest.raises(ValueError):
        engine.serve([Request(prompt=prompts[0]),
                      Request(prompt=prompts[1, :4])])   # unequal lengths


# ---------------------------------------------------------------------------
# Continuous-batching scheduler (single device)
# ---------------------------------------------------------------------------

def _requests(prompts, budgets, **kw):
    return [Request(prompt=prompts[i], max_new_tokens=m, request_id=i, **kw)
            for i, m in enumerate(budgets)]


def test_continuous_parity_and_slot_reuse(engine, prompts):
    """The oracle: continuous batching with fewer slots than requests (so
    slots MUST be retired and reused) produces token-identical outputs to
    the static reference loop."""
    budgets = (8, 3, 5)
    ref = np.asarray(engine.generate(prompts, list(budgets)))
    reqs = _requests(prompts, budgets)
    sched = ContinuousScheduler(engine, max_batch=2)
    sched.run(reqs)
    for i, r in enumerate(reqs):
        assert r.generated == ref[i, :budgets[i]].tolist(), i
        assert r.result.finish_reason == "budget"
    # 3 requests through 2 slots: the pool recycled at least one slot
    assert sched.metrics.slots_allocated == 3 > sched.max_batch
    assert sched.pool.n_free == 2                   # all retired
    assert sched.pool.committed_tokens == 0


def test_continuous_eos_parity(engine, prompts):
    ref = np.asarray(engine.generate(prompts, 8))
    eos = int(ref[0, 2])
    reqs = _requests(prompts, (8, 8, 8))
    ContinuousScheduler(engine, max_batch=2).run(reqs, eos_id=eos)
    for i, r in enumerate(reqs):
        row = ref[i]
        want = row.tolist()
        if (row == eos).any():
            want = row[:int(np.argmax(row == eos)) + 1].tolist()
            assert r.result.finish_reason == "eos"
        assert r.generated == want, i


def test_admission_never_exceeds_token_budget(engine, prompts):
    """token_budget=16 admits one request at a time (prompt 8 + budget 6 =
    14 committed tokens each): outputs stay correct and the pool's peak
    commitment respects the budget."""
    ref = np.asarray(engine.generate(prompts, 6))
    reqs = _requests(prompts, (6, 6, 6))
    sched = ContinuousScheduler(engine, max_batch=3, token_budget=16)
    sched.run(reqs)
    assert sched.pool.peak_committed <= 16
    assert sched.metrics.summary()["slot_occupancy"] <= 1 / 3 + 1e-9
    for i, r in enumerate(reqs):
        assert r.generated == ref[i].tolist(), i
    # a request that can NEVER fit the budget fails loudly, not silently
    with pytest.raises(RuntimeError, match="deadlock"):
        ContinuousScheduler(engine, max_batch=3, token_budget=8).run(
            _requests(prompts[:1], (6,)))
    # ... and one that exceeds a slot's max_len is rejected up front
    with pytest.raises(ValueError, match="max_len"):
        ContinuousScheduler(engine, max_batch=3).run(
            _requests(prompts[:1], (60,)))


def test_continuous_streaming_and_metrics(engine, prompts):
    got = {}
    reqs = _requests(prompts, (5, 2, 4))
    ContinuousScheduler(engine, max_batch=2).run(
        reqs, stream=lambda r, t: got.setdefault(r.request_id, []).append(t))
    for r in reqs:
        assert got[r.request_id] == r.generated     # streamed == final
        m = r.result.metrics
        assert m.queue_wait is not None and m.queue_wait >= 0
        assert m.ttft is not None and m.ttft >= m.queue_wait
        assert m.n_generated == len(r.generated)
        if m.n_generated >= 2:
            assert m.tpot is not None and m.tpot >= 0
    s = ContinuousScheduler(engine, max_batch=2)
    # summary schema sanity (the bench JSON derives from it)
    reqs2 = _requests(prompts, (3, 3, 3))
    s.run(reqs2)
    summ = s.metrics.summary()
    assert summ["tokens_generated"] == 9
    assert summ["throughput_tok_s"] > 0
    assert 0 < summ["slot_occupancy"] <= 1


def test_continuous_arrival_order_fifo():
    """Arrival times drive admission order (stable FIFO on ties) on an
    injected virtual clock — no wall-time dependence."""
    t = [0.0]
    clock = lambda: t[0]                                       # noqa: E731
    sleep = lambda s: t.__setitem__(0, t[0] + s)               # noqa: E731
    params = init_lm(jax.random.PRNGKey(0), TINY)
    eng = ServingEngine(params, TINY, max_len=32)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (3, 8), 0, TINY.vocab)
    ref = np.asarray(eng.generate(prompts, 4))
    reqs = _requests(prompts, (4, 4, 4))
    reqs[0].arrival_time = 1.0          # arrives LAST despite being first
    order = []
    sched = ContinuousScheduler(eng, max_batch=1, clock=clock, sleep=sleep)
    sched.run(reqs, stream=lambda r, tok: order.append(r.request_id))
    assert [i for k, i in enumerate(order) if order.index(i) == k] == [1, 2, 0]
    for i, r in enumerate(reqs):
        assert r.generated == ref[i].tolist(), i
        assert r.result.metrics.ttft >= 0
    # the late request never waited in queue before its arrival
    assert reqs[0].result.metrics.arrival_time == 1.0


def test_serve_continuous_delegation_and_replay_static(engine, prompts):
    ref = np.asarray(engine.generate(prompts, 6))
    reqs = _requests(prompts, (6, 6, 6))
    engine.serve(reqs, continuous=True, max_batch=2)
    for i, r in enumerate(reqs):
        assert r.generated == ref[i].tolist(), i
    # the instrumented static baseline is token-identical too
    reqs2, metrics = replay_static(engine, _requests(prompts, (6, 6, 6)),
                                   max_batch=2)
    for i, r in enumerate(reqs2):
        assert r.generated == ref[i].tolist(), i
    assert metrics.summary()["tokens_generated"] == 18


def test_request_result_ergonomics(engine, prompts):
    """Satellite: no mutable list default; ``generated`` is a read-only view
    of the result object; serve() fills results on the static path too."""
    r = Request(prompt=prompts[0])
    assert r.result is None and r.generated is None
    assert r.eos_id is None and r.arrival_time == 0.0
    r2 = Request(prompt=prompts[0])
    assert r.result is r2.result is None    # no shared mutable default
    reqs = [Request(prompt=prompts[i], max_new_tokens=4) for i in range(3)]
    engine.serve(reqs)
    ref = np.asarray(engine.generate(prompts, 4))
    for i, r in enumerate(reqs):
        assert isinstance(r.result, RequestResult)
        assert r.result.finish_reason == "budget"
        assert r.generated == ref[i].tolist()


def test_kv_pool_alloc_free_compact():
    pool = KVPool(TINY, max_batch=4, max_len=16)
    s0 = pool.alloc(10)
    s1 = pool.alloc(10)
    s2 = pool.alloc(10)
    assert pool.committed_tokens == 30 and pool.n_free == 1
    with pytest.raises(ValueError, match="max_len"):
        pool.can_admit(17)
    pool.free(s1)
    with pytest.raises(ValueError, match="already free"):
        pool.free(s1)
    assert pool.alloc(10) == s1             # LIFO reuse of the freed slot
    pool.free(s1)
    pool.free(s0)
    # compact packs the live slot(s) to the front and renumbers
    pool.lengths[s2] = 7
    mapping = pool.compact()
    assert mapping == {s2: 0}
    assert pool.active_slots() == [0]
    assert pool.lengths[0] == 7 and pool.n_free == 3
    assert int(pool.caches["pos"].shape[0]) == 4
    # budget exhaustion raises PoolExhausted through alloc
    small = KVPool(TINY, max_batch=2, max_len=16, token_budget=20)
    small.alloc(16)
    assert not small.can_admit(16)
    with pytest.raises(PoolExhausted):
        small.alloc(16)


def test_serve_static_rejects_mixed_eos(engine, prompts):
    """A request-level eos_id must never silently apply to batchmates that
    set none — static serving rejects mixed effective EOS ids (continuous
    mode resolves them per request)."""
    reqs = [Request(prompt=prompts[0], max_new_tokens=4, eos_id=7),
            Request(prompt=prompts[1], max_new_tokens=4)]
    with pytest.raises(ValueError, match="EOS"):
        engine.serve(reqs)
    # ...and the continuous path handles the same set fine
    engine.serve([Request(prompt=prompts[0], max_new_tokens=4, eos_id=7),
                  Request(prompt=prompts[1], max_new_tokens=4)],
                 continuous=True, max_batch=2)
    # uniform effective ids (all defaulted) still serve statically
    engine.serve([Request(prompt=prompts[0], max_new_tokens=4),
                  Request(prompt=prompts[1], max_new_tokens=4)])


def test_scheduler_reuse_accumulates_elapsed(engine, prompts):
    """serve(scheduler=...) reuse: throughput denominators accumulate busy
    time across runs instead of charging all tokens to the last run's
    span."""
    sched = ContinuousScheduler(engine, max_batch=2)
    engine.serve(_requests(prompts, (4, 4, 4)), continuous=True,
                 scheduler=sched)
    e1 = sched.metrics.elapsed
    assert e1 > 0
    engine.serve(_requests(prompts, (4, 4, 4)), continuous=True,
                 scheduler=sched)
    assert sched.metrics.tokens_generated == 24
    assert sched.metrics.elapsed > e1          # segments bank, never reset


def test_serve_driver_profile_topology(tmp_path):
    """Satellite: ``--topology profile:<path>`` fits a measured fabric and
    the metrics JSON records it (schema exercised without any mesh)."""
    from repro.launch.serve import resolve_topology, topology_facts
    samples = [[1 << 20, 1e-4], [1 << 24, 1.2e-3], [1 << 26, 4.6e-3]]
    p = tmp_path / "fabric.json"
    p.write_text(__import__("json").dumps(samples))
    topo = resolve_topology(f"profile:{p}", 8)
    assert [a.size for a in topo.axes] == [8]
    # fitted bandwidth ~ bytes/seconds slope of the samples
    assert 1e9 < topo.bottleneck_bandwidth < 1e11
    facts = topology_facts(topo, None)
    assert facts["topology"][0]["name"] == "measured"
    assert facts["bottleneck_bandwidth_gbps"] > 1
    # presets still resolve through the same entry point
    assert resolve_topology("ici_dcn", 8, n_hosts=2).axes[0].name == "dcn"


def test_dryrun_cell_meta_records_profile_fabric(tmp_path):
    """Satellite (PR 5): the dry-run cells accept a fitted profile fabric
    (``launch/dryrun.py --topology profile:<path>`` resolves through the
    same ``launch.mesh.resolve_topology``) and record it — plus the
    executed-vs-priced backward identity — in the cell meta."""
    import json
    from repro.configs import get
    from repro.launch.mesh import make_mesh
    from repro.launch.mesh import resolve_topology as resolve
    from repro.launch.steps import build_cell
    samples = [[1 << 20, 1e-4], [1 << 24, 1.2e-3], [1 << 26, 4.6e-3]]
    p = tmp_path / "fabric.json"
    p.write_text(json.dumps(samples))
    mesh = make_mesh((1, 1), ("data", "model"))
    topo = resolve(f"profile:{p}", max(mesh.shape["model"], 2))
    spec = get("gemma2-2b")
    shape = [s for s, v in spec.shapes().items()
             if v["step"] == "train"][0]
    meta = build_cell(spec, shape, mesh, topology=topo).meta
    assert meta["topology"][0]["name"] == "measured"
    assert meta["bottleneck_bandwidth_gbps"] > 1
    # the priced backward IS the executed backward (one schedule object)
    assert meta["bwd_mirrored"] is True
    assert meta["planned_bwd_switches"] == meta["planned_switches"]
    assert meta["executed_bwd_dims"][:3] == [1, 2, 1]
    assert "planned_roundtrip_seconds" in meta


REPLAN_SCRIPT = r"""
import jax, jax.numpy as jnp
import numpy as np
from repro.core.topology import Topology
from repro.models.lm import LMConfig, init_lm
from repro.parallel.partition import ParallelPlan
from repro.serving.engine import (ServingEngine, assert_kv_cache_on_mesh,
                                  _submesh)

cfg = LMConfig(name="t", n_layers=2, d_model=64, n_heads=8, n_kv_heads=4,
               head_dim=16, d_ff=128, vocab=96, dtype=jnp.float32)
params = init_lm(jax.random.PRNGKey(0), cfg)
prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 96)

ref = np.asarray(ServingEngine(params, cfg, max_len=32)
                 .generate(prompts, max_new_tokens=8))

eng = ServingEngine(params, cfg, max_len=32, mesh=_submesh(8, 1),
                    plan=ParallelPlan(mode="dsp"),
                    topology=Topology.multihost(2, 4))
assert eng.sp_degree == 8
assert eng.schedule is not None and eng.schedule.topology is eng.topology
lg, caches = eng._prefill(prompts)
assert_kv_cache_on_mesh(caches["periods"], eng.mesh, eng.plan)
out8 = np.asarray(eng.generate(prompts, max_new_tokens=8))
assert np.array_equal(out8, ref), (out8, ref)

# elastic resize 8 -> 4: the engine re-derives (plan, schedule, sharder)
eng.replan(4)
assert eng.sp_degree == 4
assert [(a.name, a.size) for a in eng.topology.axes] == [("dcn", 2),
                                                         ("ici", 2)]
lg, caches = eng._prefill(prompts)
assert_kv_cache_on_mesh(caches["periods"], eng.mesh, eng.plan)
out4 = np.asarray(eng.generate(prompts, max_new_tokens=8))
assert np.array_equal(out4, ref), (out4, ref)

# live-cache migration path: caches resharded onto the new mesh still decode
lg, caches = eng._prefill(prompts)
moved = eng.shard_caches(caches)
lg2, _ = eng._decode(jnp.argmax(lg[:, -1], -1)[:, None], moved)
assert lg2.shape == lg.shape

# downsize to 1 device degenerates the live plan; a later upsize must
# restore the SHARDED plan and the original ICIxDCN fabric, not the
# degenerate mode="none" / topology=None state
eng.replan(1)
assert eng.mesh is None and eng.plan.mode == "none"
eng.replan(4)
assert eng.plan.mode == "dsp" and eng.sp_degree == 4
assert [a.name for a in eng.topology.axes] == ["dcn", "ici"]
out4b = np.asarray(eng.generate(prompts, max_new_tokens=8,
                                check_sharding=True))
assert np.array_equal(out4b, ref)
print("replan OK")
"""


def test_replan_sp_degree_change_matches_unsharded_reference():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"     # simulated devices, never the chip
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", REPLAN_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, (
        f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr[-4000:]}")
    assert "replan OK" in proc.stdout
