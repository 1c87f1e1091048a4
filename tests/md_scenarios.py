"""Multi-device test scenarios.  Run in a SUBPROCESS with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (tests/test_multidevice.py
drives this); never import from the main pytest process, which must keep the
1-device default.

Each scenario asserts internally and prints '<name> OK'.
"""
import sys

import numpy as np


def _mesh(shape, axes):
    from repro.launch.mesh import make_mesh
    return make_mesh(shape, axes)


# fp32 parity bound between a sequence-sharded and an unsharded (or
# differently sharded) run of the same math.  Not bitwise: XLA:CPU's dot
# emitter picks its accumulation order by operand shape, so a matmul over
# a sequence shard can round its rows differently from the same rows in the
# full matmul (a few ulp, ~1e-7 relative, observed with jax 0.9.0).
FP32_RTOL = 1e-5


def _rel_err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max()) / max(float(np.abs(b).max()), 1e-9)


def scenario_dsp_primitives():
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core import dynamic_switch, split, gather
    mesh = _mesh((2, 4), ("data", "model"))
    x = jnp.arange(2 * 8 * 8 * 6, dtype=jnp.float32).reshape(2, 8, 8, 6)

    def body(x):
        y = dynamic_switch(x, 1, 2)
        z = dynamic_switch(y, 2, 1)
        return split(gather(z, 1), 1)

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(None, "model"),
                              out_specs=P(None, "model"), check_vma=False))
    assert np.allclose(f(x), x)

    # switch changes local shapes as Table 2 prescribes
    def probe(x):
        y = dynamic_switch(x, 1, 2)
        return jnp.asarray(y.shape)

    g = jax.jit(jax.shard_map(lambda x: probe(x), mesh=mesh,
                              in_specs=P(None, "model"), out_specs=P(None),
                              check_vma=False))
    local = np.asarray(g(x))
    assert tuple(local) == (2, 8, 2, 6)          # T restored, S divided


def scenario_t2d_modes():
    import jax, jax.numpy as jnp
    from repro.models.transformer2d import (T2DConfig, init_t2d, forward,
                                            make_spmd_forward)
    from repro.analysis.roofline import parse_collectives
    cfg = T2DConfig(name="t", n_layers=4, d_model=64, n_heads=4, d_ff=128,
                    in_dim=16, dtype=jnp.float32)
    params = init_t2d(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16, 16))
    t = jnp.array([0.1, 0.5])
    ref = forward(params, x, t, cfg, backend="ref", remat=False)
    mesh = _mesh((2, 4), ("data", "model"))
    expected_a2a = {"dsp": 2, "ulysses": 4, "ulysses_fused": 2}
    for mode in ["dsp", "ulysses", "ulysses_fused", "ring", "megatron"]:
        fn = make_spmd_forward(cfg, mesh, mode=mode, backend="ref")
        out = jax.jit(fn)(params, x, t)
        rel = float(jnp.abs(out - ref).max()) / float(jnp.abs(ref).max())
        assert rel < 2e-4, (mode, rel)
        txt = jax.jit(fn).lower(params, x, t).compile().as_text()
        stats = parse_collectives(txt)
        a2a = stats.by_kind_count.get("all-to-all", 0)
        if mode in expected_a2a:
            # per layer-pair (scan body): paper Table 3 counts
            assert a2a == expected_a2a[mode] * (cfg.n_layers // 2), (
                mode, a2a, stats.by_kind_count)
        if mode == "ring":
            assert stats.by_kind_count.get("collective-permute", 0) > 0
        if mode == "megatron":
            assert stats.by_kind_count.get("all-gather", 0) >= 2 * (
                cfg.n_layers // 2)
            assert stats.by_kind_count.get("reduce-scatter", 0) >= 2 * (
                cfg.n_layers // 2)

    # comm volume ordering on identical workload (paper Table 3):
    vol = {}
    for mode in ["dsp", "ulysses", "megatron", "ring"]:
        fn = make_spmd_forward(cfg, mesh, mode=mode, backend="ref")
        txt = jax.jit(fn).lower(params, x, t).compile().as_text()
        vol[mode] = parse_collectives(txt).bytes_per_device
    assert vol["dsp"] < vol["ulysses"] < vol["megatron"]
    assert vol["dsp"] < vol["ring"]


def scenario_lm_parallel_equivalence():
    import jax, jax.numpy as jnp
    from repro.models.lm import LMConfig, init_lm, forward
    from repro.models.ssm import SSMConfig
    from repro.parallel.partition import ParallelPlan, make_sharder
    sc = SSMConfig(d_model=64, d_inner=128, head_dim=16, d_state=32,
                   n_groups=4, chunk=16)
    cfg = LMConfig(name="t", n_layers=8, d_model=64, n_heads=4, n_kv_heads=2,
                   head_dim=16, d_ff=96, vocab=128, ssm_every=4,
                   ssm_attn_offset=1, n_experts=4, top_k=2, moe_every=2,
                   moe_offset=1, ssm_cfg=sc, dtype=jnp.float32)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 128)
    ref, _ = forward(params, tokens, cfg, backend="ref", remat=False)
    mesh = _mesh((2, 4), ("data", "model"))
    for mode, ep in [("dsp", True), ("tp", False)]:
        sharder = make_sharder(mesh, ParallelPlan(mode=mode, ep=ep))
        out, _ = jax.jit(lambda p, t: forward(
            p, t, cfg, sharder=sharder, backend="ref", remat=False))(params,
                                                                     tokens)
        rel = float(jnp.abs(out - ref).max()) / float(jnp.abs(ref).max())
        assert rel < 2e-3, (mode, rel)


def scenario_decode_sharded():
    import jax, jax.numpy as jnp
    from repro.models.lm import (LMConfig, init_lm, forward_prefill,
                                 forward_decode)
    from repro.parallel.partition import ParallelPlan, make_sharder
    cfg = LMConfig(name="t", n_layers=2, d_model=64, n_heads=8, n_kv_heads=4,
                   head_dim=16, d_ff=128, vocab=96, dtype=jnp.float32)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 96)
    lg0, c0 = forward_prefill(params, toks[:, :12], cfg, backend="ref",
                              remat=False)

    def grow(c, pad):
        def f(a):
            if a.ndim == 5:
                return jnp.pad(a, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
            return a
        return {"pos": c["pos"],
                "periods": jax.tree_util.tree_map(f, c["periods"])}

    c0 = grow(c0, 4)
    lg_ref, _ = forward_decode(params, toks[:, 12:13], c0, cfg, backend="ref")

    mesh = _mesh((2, 4), ("data", "model"))
    sharder = make_sharder(mesh, ParallelPlan(mode="dsp"))
    lg1, c1 = forward_prefill(params, toks[:, :12], cfg, sharder=sharder,
                              backend="ref", remat=False)
    c1 = grow(c1, 4)
    lg_sh, _ = jax.jit(lambda p, t, c: forward_decode(
        p, t, c, cfg, sharder=sharder, backend="ref"))(params,
                                                       toks[:, 12:13], c1)
    rel = float(jnp.abs(lg_sh - lg_ref).max()) / float(jnp.abs(lg_ref).max())
    assert rel < 2e-3, rel


def scenario_elastic_checkpoint():
    import tempfile
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.train.checkpoint import CheckpointManager
    from repro.models.lm import LMConfig, init_lm
    cfg = LMConfig(name="t", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                   head_dim=16, d_ff=128, vocab=128, dtype=jnp.float32)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=2, async_save=False)
        mgr.save(7, {"params": params}, blocking=True)
        # restore onto an 8-device mesh with FSDP sharding = elastic restart
        mesh = _mesh((4, 2), ("data", "model"))
        from repro.parallel.partition import ParallelPlan, param_pspecs
        specs = param_pspecs(params, ParallelPlan(mode="dsp"))
        template = jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, s)),
            params, specs)
        step, tree = mgr.restore({"params": template})
        assert step == 7
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(tree["params"])):
            assert np.allclose(np.asarray(a), np.asarray(b))
        # restored leaves actually carry the new sharding
        leaf = tree["params"]["embed"]["table"]
        assert leaf.sharding.mesh.shape["data"] == 4


def scenario_elastic_train_resize():
    """Elastic training survives a mid-run SP resize: scanned-LM training on
    the 8-device mesh, plan-aware checkpoint at step k, resize to 4 devices
    via ``Trainer.replan`` (re-solves the schedule on the resized fabric,
    migrates params + AdamW state), continue to 2k — the loss curve matches
    an uninterrupted 8-device run to ``FP32_RTOL``, and the restored +
    migrated state is bit-identical to what was saved (pure data
    movement).  Losses and final params are not bitwise: after the resize
    every matmul runs over shards of another size, whose fp32 rounding
    differs (``FP32_RTOL``)."""
    import tempfile
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro.core.topology import Topology
    from repro.data.pipeline import DataConfig, make_batch
    from repro.models.lm import LMConfig, dsp_schedule, init_lm, lm_loss
    from repro.optim.adamw import OptConfig
    from repro.parallel.partition import (ParallelPlan, make_sharder,
                                          param_pspecs)
    from repro.train.trainer import ElasticSpec, Trainer, TrainerConfig

    cfg = LMConfig(name="t", n_layers=2, d_model=64, n_heads=8, n_kv_heads=8,
                   head_dim=8, d_ff=128, vocab=96, dtype=jnp.float32)
    plan = ParallelPlan(mode="dsp", shard_vocab=False)
    dcfg = DataConfig(task="lm_shift", vocab=96, seq=32, batch=2)
    opt = OptConfig(peak_lr=1e-3, warmup_steps=2, total_steps=6)
    k, total = 3, 6

    def make_loss(mesh, sharder, schedule):
        return lambda p, b: lm_loss(p, b, cfg, sharder=sharder,
                                    backend="ref")

    def solve_schedule(sp, topo):
        return dsp_schedule(cfg, sp, seq=32, batch=2, topology=topo,
                            joint=True)

    def make_trainer(total_steps, ckpt_dir, ckpt_every):
        mesh = _mesh((2, 4), ("data", "model"))
        params = init_lm(jax.random.PRNGKey(0), cfg)
        specs = param_pspecs(params, plan, axis_sizes=dict(mesh.shape))
        params = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            params, specs)
        schedule = solve_schedule(4, Topology.flat_ici(4))
        sharder = make_sharder(mesh, plan, schedule=schedule)
        return Trainer(
            loss_fn=make_loss(mesh, sharder, schedule), params=params,
            opt_cfg=opt,
            cfg=TrainerConfig(total_steps=total_steps, log_every=1,
                              ckpt_every=ckpt_every),
            data_fn=lambda s: make_batch(dcfg, s),
            ckpt_dir=ckpt_dir, schedule=schedule, mesh=mesh,
            elastic=ElasticSpec(make_loss=make_loss,
                                solve_schedule=solve_schedule, plan=plan))

    def host(tree):
        return jax.tree_util.tree_map(
            lambda x: np.asarray(jax.device_get(x)), tree)

    def bit_equal(a, b, what):
        la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
        assert len(la) == len(lb), what
        for x, y in zip(la, lb):
            x, y = np.asarray(x), np.asarray(y)
            assert x.dtype == y.dtype and x.shape == y.shape, what
            assert x.tobytes() == y.tobytes(), what

    # uninterrupted 8-device baseline through step 2k
    base = make_trainer(total, None, 0)
    base_losses = [l for _, l in base.run()["history"]]
    assert len(base_losses) == total

    with tempfile.TemporaryDirectory() as d:
        # run 1: 8 devices, checkpoint at step k, stop
        t1 = make_trainer(k, d, k)
        losses1 = [l for _, l in t1.run()["history"]]
        saved = {"params": host(t1.params), "opt": host(t1.opt_state)}

        # the manifest records the layouts, the plan and the fabric
        step, man = t1.ckpt.load_manifest()
        assert step == k and man["format"] == "dsp-ckpt-v1"
        recs = {r["key"]: r for r in man["leaves"]}
        table = recs["params/embed/table"]
        assert table["sharded_dims"], table    # FSDP actually sharded it
        assert len(table["shards"]) > 1
        pd = man["plan"]
        dims = pd["fwd"] if pd["kind"] == "joint" else pd["dims"]
        assert tuple(dims) == tuple(t1.schedule.dims)
        topo = Topology.from_dict(man["topology"])
        assert topo == t1.schedule.topology

        # run 2: fresh process state, resume at k, RESIZE to 4, run to 2k
        t2 = make_trainer(total, d, 0)
        t2.try_resume()
        assert t2.start_step == k
        bit_equal({"params": host(t2.params), "opt": host(t2.opt_state)},
                  saved, "restore must be shard-exact")
        t2.replan(4)
        assert t2.mesh.shape == {"data": 2, "model": 2}
        assert t2.schedule is not None and t2.schedule.topology.size == 2
        bit_equal({"params": host(t2.params), "opt": host(t2.opt_state)},
                  saved, "migration must be pure layout movement")
        losses2 = [l for _, l in t2.run()["history"]]

    resized = losses1 + losses2
    assert len(resized) == total
    for t, (a, b) in enumerate(zip(base_losses, resized)):
        assert _rel_err(b, a) < FP32_RTOL, (
            t, a, b, "loss curve must stay aligned across the resize")

    for a, b in zip(jax.tree_util.tree_leaves(host(base.params)),
                    jax.tree_util.tree_leaves(host(t2.params))):
        assert _rel_err(b, a) < FP32_RTOL


def scenario_joint_bwd_parity():
    """Planned-backward executor on a REAL 8-device mesh: t2d training-loss
    gradients through the custom_vjp boundaries (both a mirrored joint plan
    and a forced non-mirrored backward) must match the plain mirrored path,
    with the activations genuinely sequence-sharded."""
    import dataclasses
    import jax, jax.numpy as jnp
    from repro.models.transformer2d import (T2DConfig, dsp_schedule, init_t2d,
                                            t2d_loss)
    cfg = T2DConfig(name="t", n_layers=4, d_model=64, n_heads=4, d_ff=128,
                    in_dim=16, dtype=jnp.float32)
    params = init_t2d(jax.random.PRNGKey(0), cfg)
    batch = {"x": jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16, 16)),
             "t": jnp.array([0.1, 0.5]),
             "target": jax.random.normal(jax.random.PRNGKey(2),
                                         (2, 8, 16, 16))}
    mesh = _mesh((2, 4), ("data", "model"))

    def grads(**kw):
        f = jax.jit(jax.grad(lambda p: t2d_loss(
            p, batch, cfg, mesh=mesh, backend="ref", remat=False, **kw)[0]))
        return f(params)

    g_ref = grads()
    g_joint = grads(joint=True)
    ps = dsp_schedule(cfg, 4, t_len=8, s_len=16, batch=2)
    forced = dataclasses.replace(ps.schedule,
                                 bwd_dims=ps.schedule.dims[::-1])
    g_forced = grads(schedule=forced.unrolled())
    for other in (g_joint, g_forced):
        for a, b in zip(jax.tree_util.tree_leaves(g_ref),
                        jax.tree_util.tree_leaves(other)):
            a, b = np.asarray(a), np.asarray(b)
            denom = max(float(np.abs(a).max()), 1e-6)
            assert float(np.abs(a - b).max()) / denom < 2e-4


def scenario_scan_joint_bwd_parity():
    """Planned backward under ``lax.scan`` on a REAL 8-device mesh: the
    scanned-LM train step under a joint plan — and under a FORCED
    non-mirrored joint plan (per-period custom_vjp boundaries through the
    Sharder hooks) — must reproduce the unsharded reference: losses and
    gradients to ``FP32_RTOL`` (matmuls over sequence shards round
    differently, and the weight-grad contractions psum over shards; the
    single-device tier in tests/test_scan_joint.py pins the grads
    BIT-identical where layouts alone change)."""
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.models.lm import LMConfig, dsp_schedule, init_lm, lm_loss
    from repro.parallel.partition import ParallelPlan, make_sharder

    cfg = LMConfig(name="t", n_layers=2, d_model=64, n_heads=8, n_kv_heads=8,
                   head_dim=8, d_ff=128, vocab=96, dtype=jnp.float32)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 96)
    batch = {"tokens": toks, "labels": toks}

    def run(sharder):
        f = jax.jit(jax.value_and_grad(lambda p: lm_loss(
            p, batch, cfg, sharder=sharder, backend="ref", remat=False)[0]))
        loss, grads = f(params)
        return np.asarray(loss), grads

    ref_loss, ref_grads = run(None)                     # unsharded reference
    mesh = _mesh((2, 4), ("data", "model"))
    plan = ParallelPlan(mode="dsp", shard_vocab=False)
    mirrored = dsp_schedule(cfg, 4, seq=32, batch=2, joint=True)
    assert mirrored.mirrored
    forced = dsp_schedule(cfg, 4, seq=32, batch=2, joint=True,
                          bwd_dims=(2, 2, 2))
    assert not forced.mirrored
    mir_loss, mir_grads = run(make_sharder(mesh, plan, schedule=mirrored))
    f_loss, f_grads = run(make_sharder(mesh, plan, schedule=forced))

    # losses: sharded vs unsharded AND forced vs mirrored
    assert _rel_err(mir_loss, ref_loss) < FP32_RTOL, (ref_loss, mir_loss)
    assert _rel_err(f_loss, ref_loss) < FP32_RTOL, (ref_loss, f_loss)

    def close(a_tree, b_tree):
        for a, b in zip(jax.tree_util.tree_leaves(a_tree),
                        jax.tree_util.tree_leaves(b_tree)):
            assert _rel_err(b, a) < FP32_RTOL

    close(ref_grads, mir_grads)
    close(mir_grads, f_grads)
    close(ref_grads, f_grads)


def scenario_grad_allreduce_compression():
    """DP gradients with int8 EF compression on an explicit pod-style axis."""
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.optim.compress import quantize_int8, dequantize_int8
    mesh = _mesh((8,), ("pod",))
    w = jnp.linspace(-1, 1, 8 * 4096).reshape(8, 4096)

    def grad_allreduce(g_local):
        q, scale = quantize_int8(g_local)
        deq = dequantize_int8(q, scale)
        return jax.lax.pmean(deq, "pod")

    f = jax.jit(jax.shard_map(grad_allreduce, mesh=mesh, in_specs=P("pod"),
                              out_specs=P("pod"), check_vma=False))
    out = f(w)
    want = jnp.broadcast_to(w.mean(0), w.shape)
    err = float(jnp.abs(out - want).max())
    assert err < 1e-2, err


def scenario_continuous_serving_sharded():
    """Continuous batching on the 8-device mesh: the slot pool stays
    sequence-sharded through admissions and retirements
    (assert_kv_cache_on_mesh after every step), tokens match the unsharded
    static reference bit-for-bit, and a mid-flight drain-and-migrate replan
    (8 -> 4 devices) changes neither."""
    import jax, jax.numpy as jnp
    from repro.core.topology import Topology
    from repro.models.lm import LMConfig, init_lm
    from repro.parallel.partition import ParallelPlan
    from repro.serving.engine import Request, ServingEngine, _submesh
    from repro.serving.scheduler import ContinuousScheduler

    cfg = LMConfig(name="t", n_layers=2, d_model=64, n_heads=8, n_kv_heads=4,
                   head_dim=16, d_ff=128, vocab=96, dtype=jnp.float32)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 96)
    budgets = (8, 3, 6, 8)
    ref = np.asarray(ServingEngine(params, cfg, max_len=32)
                     .generate(prompts, list(budgets)))

    eng = ServingEngine(params, cfg, max_len=32, mesh=_submesh(8, 1),
                        plan=ParallelPlan(mode="dsp"),
                        topology=Topology.multihost(2, 4))
    assert eng.sp_degree == 8
    reqs = [Request(prompt=prompts[i], max_new_tokens=budgets[i],
                    request_id=i) for i in range(4)]
    sched = ContinuousScheduler(eng, max_batch=2)     # 4 reqs, 2 slots
    replanned = []

    def on_step(s, k):
        s.pool.assert_on_mesh()        # seq-sharded through the whole run
        if k == 3:                     # elastic resize with slots LIVE
            s.replan(4)
            replanned.append(k)

    sched.run(reqs, on_step=on_step)
    assert replanned == [3]
    assert eng.sp_degree == 4
    assert sched.metrics.slots_allocated == 4 > sched.max_batch
    for i, r in enumerate(reqs):
        assert r.generated == ref[i, :budgets[i]].tolist(), (
            i, r.generated, ref[i, :budgets[i]].tolist())


def scenario_paged_serving_sharded():
    """The paged tier on the 8-device mesh: block-pool KV stays
    sequence-sharded through chunked prefills, admissions and retirements
    (assert_on_mesh after every step), tokens match the unsharded static
    reference bit-for-bit, a mid-flight replan (8 -> 4) changes neither,
    and the compiled decode step shows EXACTLY the slot path's collectives
    — block alloc/free/share is host bookkeeping, zero extra
    communication."""
    import jax, jax.numpy as jnp
    from repro.analysis.roofline import parse_collectives
    from repro.core.topology import Topology
    from repro.models.lm import LMConfig, init_lm
    from repro.parallel.partition import ParallelPlan
    from repro.serving.engine import Request, ServingEngine, _submesh
    from repro.serving.kv_pool import KVPool
    from repro.serving.scheduler import PagedScheduler

    cfg = LMConfig(name="t", n_layers=2, d_model=64, n_heads=8, n_kv_heads=4,
                   head_dim=16, d_ff=128, vocab=96, dtype=jnp.float32)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 96)
    budgets = (8, 3, 6, 8)
    ref = np.asarray(ServingEngine(params, cfg, max_len=32)
                     .generate(prompts, list(budgets)))

    eng = ServingEngine(params, cfg, max_len=32, mesh=_submesh(8, 1),
                        plan=ParallelPlan(mode="dsp"),
                        topology=Topology.multihost(2, 4))
    assert eng.sp_degree == 8

    # -- compiled-HLO pin: the paged decode step's collectives are EXACTLY
    # the slot decode step's (all-reduce only; the block-table gather and
    # scatter stay device-local on the sequence-sharded leaves) ------------
    sched = PagedScheduler(eng, max_batch=2, block_size=8, prefill_chunk=8)
    tok = jnp.zeros((2, 1), jnp.int32)
    slot_caches = KVPool(cfg, 2, 32, mesh=eng.mesh, plan=eng.plan).caches
    by_arm = {}
    for arm, caches in (("slot", slot_caches), ("paged", sched.pool.caches)):
        hlo = (jax.jit(lambda t, c: eng._decode_impl(t, c))
               .lower(tok, caches).compile().as_text())
        by_arm[arm] = {
            k: int(v)
            for k, v in parse_collectives(hlo).by_kind_count.items() if v}
    assert not set(by_arm["paged"]) & {"all-gather", "all-to-all",
                                       "reduce-scatter"}, by_arm
    assert by_arm["paged"] == by_arm["slot"], by_arm

    reqs = [Request(prompt=prompts[i], max_new_tokens=budgets[i],
                    request_id=i) for i in range(4)]
    replanned = []

    def on_step(s, k):
        s.pool.assert_on_mesh()        # seq-sharded through the whole run
        if k == 3:                     # elastic resize with blocks LIVE
            s.replan(4)
            replanned.append(k)

    sched.run(reqs, on_step=on_step)
    assert replanned == [3]
    assert eng.sp_degree == 4
    assert sched.metrics.slots_allocated == 4 > sched.max_batch
    assert sched.metrics.prefill_chunk_steps >= 4   # chunked prefill ran
    assert sched.pool.free_blocks > 0
    for i, r in enumerate(reqs):
        assert r.generated == ref[i, :budgets[i]].tolist(), (
            i, r.generated, ref[i, :budgets[i]].tolist())


def scenario_layout2d_t2d():
    """First-class 2D layouts on the (2, 4) sp2d mesh.  Three contracts:

    1. PARITY — ``forward2d`` executing the planned T x S dim-pair layouts
       matches the jitted 1D reference to ``FP32_RTOL`` (layout changes
       never change the math; only the shard-size-dependent rounding of
       the local matmuls does), on the full (2, 4) grid and on a
       degenerate (1, 8) grid (where the planner collapses to the 1D DP).
    2. HLO — the compiled forward carries EXACTLY one sub-axis all-to-all
       per changed axis per planned switch (``expected_carry_collectives``)
       and NOTHING else: no all-gather, reduce-scatter or
       collective-permute, zero collectives on unchanged axes.
    3. MID-FLIGHT REPLAN — an elastic resize (8 -> 4) fired while a
       chunked prefill is mid-prompt on the sharded paged tier keeps every
       request's tokens bit-identical to the static oracle (the window the
       paged_serving_sharded scenario never hits: its replan lands with
       ``_prefilling`` drained)."""
    import jax, jax.numpy as jnp
    from repro.analysis.roofline import parse_collectives
    from repro.core.schedule import ScheduleExecutor2D
    from repro.launch.mesh import make_sp2d_mesh, mesh_topology
    from repro.models.transformer2d import (T2DConfig, init_t2d,
                                            dsp2d_schedule, forward,
                                            forward2d)

    cfg = T2DConfig(name="t", n_layers=4, d_model=32, n_heads=4, d_ff=64,
                    in_dim=8, dtype=jnp.float32)
    B, T, S = 2, 4, 8
    params = init_t2d(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, S, cfg.in_dim))
    t = jnp.array([0.1, 0.5])
    ref = jax.jit(lambda p, xx, tt: forward(
        p, xx, tt, cfg, backend="ref", remat=False))(params, x, t)
    # the degenerate grid runs T=8 so the collapsed 1D plan's dims divide
    # by the full SP degree (the 1D DP never consults Stage.extents, and
    # the delegation reproduces it bit-for-bit, warts and all)
    x8 = jax.random.normal(jax.random.PRNGKey(2), (B, 8, S, cfg.in_dim))
    ref8 = jax.jit(lambda p, xx, tt: forward(
        p, xx, tt, cfg, backend="ref", remat=False))(params, x8, t)

    for grid, xin, want in (((2, 4), x, ref), ((1, 8), x8, ref8)):
        mesh = make_sp2d_mesh(*grid)
        fn = jax.jit(lambda p, xx, tt, m=mesh: forward2d(
            p, xx, tt, cfg, mesh=m, remat=False))
        out = fn(params, xin, t)
        assert _rel_err(out, want) < FP32_RTOL, grid

    # -- compiled contract on the full (2, 4) grid -------------------------
    mesh = make_sp2d_mesh(2, 4)
    topo = mesh_topology(mesh)     # sp2d auto-detection: outer DCN x ICI
    assert [(a.name, a.size) for a in topo.axes] == [("dcn", 2), ("ici", 4)]
    psched = dsp2d_schedule(cfg, (2, 4), t_len=T, s_len=S, batch=B)
    # the planned period mixes inner-only and outer-only switches
    ex = ScheduleExecutor2D(psched, backend="auto", mesh=mesh)
    expected = ex.expected_carry_collectives(cfg.n_layers // 2)
    assert expected == {"all-to-all": 8}, expected
    fn = jax.jit(lambda p, xx, tt: forward2d(
        p, xx, tt, cfg, mesh=mesh, remat=False))
    stats = parse_collectives(fn.lower(params, x, t).compile().as_text())
    got = {k: int(v) for k, v in stats.by_kind_count.items() if v}
    assert got == expected, (got, expected)

    # -- mid-flight replan: resize lands BETWEEN two prompt chunks ---------
    from repro.core.topology import Topology
    from repro.models.lm import LMConfig, init_lm
    from repro.parallel.partition import ParallelPlan
    from repro.serving.engine import Request, ServingEngine, _submesh
    from repro.serving.scheduler import PagedScheduler

    lm = LMConfig(name="t", n_layers=2, d_model=64, n_heads=8, n_kv_heads=4,
                  head_dim=16, d_ff=128, vocab=96, dtype=jnp.float32)
    lmp = init_lm(jax.random.PRNGKey(0), lm)
    long_p = jax.random.randint(jax.random.PRNGKey(9), (16,), 0, 96)
    short_p = jax.random.randint(jax.random.PRNGKey(10), (8,), 0, 96)
    ref0 = np.asarray(ServingEngine(lmp, lm, max_len=32)
                      .generate(short_p[None], [8]))[0]
    ref1 = np.asarray(ServingEngine(lmp, lm, max_len=32)
                      .generate(long_p[None], [8]))[0]
    eng = ServingEngine(lmp, lm, max_len=32, mesh=_submesh(8, 1),
                        plan=ParallelPlan(mode="dsp"),
                        topology=Topology.multihost(2, 4))
    reqs = [Request(prompt=short_p, max_new_tokens=8, request_id=0),
            Request(prompt=long_p, max_new_tokens=8, request_id=1)]
    sched = PagedScheduler(eng, max_batch=2, block_size=8, prefill_chunk=4)
    forced = []

    def on_step(s, k):
        s.pool.assert_on_mesh()
        if k == 2:
            pf = s._prefilling[0]      # a prefill is mid-prompt RIGHT NOW
            assert 0 < pf.done < len(pf.prompt), (pf.done, len(pf.prompt))
            s.replan(4)
            forced.append(k)

    sched.run(reqs, on_step=on_step)
    assert forced == [2] and eng.sp_degree == 4
    assert reqs[0].generated == ref0[:8].tolist()
    assert reqs[1].generated == ref1[:8].tolist()


SCENARIOS = {name[len("scenario_"):]: fn
             for name, fn in list(globals().items())
             if name.startswith("scenario_")}

if __name__ == "__main__":
    name = sys.argv[1]
    SCENARIOS[name]()
    print(f"{name} OK")
