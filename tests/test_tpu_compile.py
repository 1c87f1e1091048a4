"""Compile the main path's Pallas kernel for a TPU v5e that is described,
not attached: the TPU compiler refuses here what interpret mode on the CPU
accepts (misaligned tiles, too much VMEM).  Shapes are the 720M DiT's
(16 heads of 72) at the training smoke shape and the paper's spatial
extent, and the 3B DiT's (32 heads of 64) as one chip of four sees it.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  Keep all such compiles in this one file.
"""
import contextlib
import functools
import os

import pytest

H, D = 16, 72        # transformer2d-720m: d_model 1152 / 16 heads


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile_fwd(one_chip, batch, q_len, kv_len, kv_valid, heads=H, dh=D,
                 dtype="bfloat16"):
    """The kernel on the tiles ``ops.flash_tiles`` picks for the shape."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_attention import flash_attention_fwd
    from repro.kernels.ops import flash_tiles
    q = jax.ShapeDtypeStruct((batch, heads, q_len, dh), dtype,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((batch, heads, kv_len, dh), dtype,
                              sharding=one_chip)
    bq, bk = flash_tiles(q_len, kv_valid)
    fn = functools.partial(flash_attention_fwd, kv_len=kv_valid,
                           block_q=bq, block_k=bk, interpret=False)
    return jax.jit(fn).lower(q, kv, kv).compile().as_text()


@pytest.mark.parametrize("batch,s_len,heads,dh,dtype", [
    (16, 256, H, D, "bfloat16"), (1, 4096, H, D, "bfloat16"),
    # the DiT's activations are f32, so its cells feed the kernel f32
    (16, 256, H, D, "float32"), (4, 1024, 32, 64, "float32"),
    (1, 4096, H, D, "float32"),
], ids=["S256", "S4096", "S256-f32", "S1024-3b-f32", "S4096-f32"])
def test_spatial_stage_kernel_compiles(one_chip, batch, s_len, heads, dh,
                                       dtype):
    # spatial attention: (B*T) sequences of S tokens
    hlo = _compile_fwd(one_chip, batch, s_len, s_len, s_len, heads=heads,
                       dh=dh, dtype=dtype)
    assert "tpu_custom_call" in hlo


def test_temporal_stage_kernel_compiles(one_chip):
    # temporal attention (T=16, one KV block) runs as XLA's attention
    # (kernels/ops.py), so the kernel's shape in the 3B four-chip cell is
    # spatial attention per chip under a (1, 4) DSP mesh: the switch leaves
    # each chip B*T/4 = 4 frames of all S=1024 tokens, 32 heads of 64
    hlo = _compile_fwd(one_chip, 4, 1024, 1024, 1024, heads=32, dh=64)
    assert "tpu_custom_call" in hlo


def _lower_sharded_step(topo):
    """The 720M train step at full width, cut to one spatial and one
    temporal block, lowered for a (1, 4) DSP mesh of the v5e:2x2 with the
    kernel compiled (not interpreted); returns (lowered, schedule, mesh)."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
    from repro import configs
    from repro.kernels import flash_attention as fa, ops
    from repro.launch.mesh import mesh_topology
    from repro.models.transformer2d import dsp_schedule, init_t2d, t2d_loss
    from repro.optim.adamw import OptConfig, init_opt_state
    from repro.parallel.partition import param_pspecs
    from repro.train.trainer import make_train_step

    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    spec = configs.get("transformer2d-720m")
    cfg = dataclasses.replace(spec.config, n_layers=2)
    b, t, s = 1, 16, 256
    psched = dsp_schedule(cfg, 4, t_len=t, s_len=s, batch=b,
                          topology=mesh_topology(mesh, "ici"), joint=True)
    opt_cfg = OptConfig()

    def placed(tree):
        specs = param_pspecs(tree, spec.plan, axis_sizes=dict(mesh.shape))
        return jax.tree_util.tree_map(
            lambda a, p: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                              sharding=NamedSharding(mesh, p)),
            tree, specs)

    params = jax.eval_shape(lambda: init_t2d(jax.random.PRNGKey(0), cfg))
    opt = jax.eval_shape(lambda p: init_opt_state(p, opt_cfg), params)
    rep = NamedSharding(mesh, P())
    video = jax.ShapeDtypeStruct((b, t, s, cfg.in_dim), jnp.float32,
                                 sharding=rep)
    batch = {"x": video, "t": jax.ShapeDtypeStruct((b,), jnp.float32,
                                                   sharding=rep),
             "target": video}

    def loss_fn(p, bb):
        return t2d_loss(p, bb, cfg, mesh=mesh, schedule=psched)

    step = jax.jit(make_train_step(loss_fn, opt_cfg), donate_argnums=(0, 1))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "flash_attention_fwd", functools.partial(
            fa.flash_attention_fwd, interpret=False))
        lowered = step.lower(placed(params), placed(opt), batch)
    return lowered, psched, mesh


@pytest.fixture(scope="module")
def sharded_step(topo):
    """(compiled HLO text, schedule, mesh) of ``_lower_sharded_step``."""
    lowered, psched, mesh = _lower_sharded_step(topo)
    return lowered.compile().as_text(), psched, mesh


def test_planned_switches_of_the_sharded_train_step_carry_their_scope(
        sharded_step):
    """Every all-to-all of the forward, and of remat's recompute, is a
    planned switch and carries ``dsp_switch``.  Each planned backward
    switch lands on a block-end anchor (the mirrored plan's transposed
    boundary keeps the cotangent's layout; see
    tests/test_hlo_collectives.py).  The all-to-alls that no plan accounts
    for carry ``mlp``: they come from the FFN's backward.  ``pytest -s``
    prints the table by scope."""
    import collections
    from repro.analysis.roofline import op_scope, parse_data_collectives
    from repro.core.layout import from_mesh
    from repro.core.schedule import ScheduleExecutor

    hlo, psched, mesh = sharded_step
    assert "tpu_custom_call" in hlo

    def leg(ln):
        if "rematted_computation" in ln:
            return "recompute"
        return "bwd" if "transpose(" in ln else "fwd"

    def a2a(where):
        return parse_data_collectives(hlo, where=where).by_kind_count.get(
            "all-to-all", 0)

    table = collections.Counter(
        (op_scope(ln) or "none", leg(ln)) for ln in hlo.splitlines()
        if " all-to-all(" in ln or " all-to-all-start(" in ln)
    print("\nall-to-alls by (scope, leg):", dict(table))
    ex = ScheduleExecutor(psched, backend="auto", ctx=from_mesh(mesh))
    planned_fwd = ex.expected_collectives(1)["all-to-all"]
    planned_bwd = ex.expected_bwd_collectives(1)["all-to-all"]
    switch = a2a(lambda ln: op_scope(ln) == "dsp_switch")
    fwd = a2a(lambda ln: leg(ln) == "fwd")
    recompute = a2a(lambda ln: leg(ln) == "recompute")
    assert fwd == planned_fwd
    assert 0 < recompute <= planned_fwd
    assert switch == fwd + recompute
    assert a2a(lambda ln: leg(ln) == "bwd" and "/sharding_constraint" in ln
               and op_scope(ln) in ("spatial", "temporal")) == planned_bwd
    unplanned = {sc for (sc, lg) in table
                 if lg == "bwd" and sc not in ("spatial", "temporal")}
    assert unplanned == {"mlp"}, table


def test_zero_scope_names_the_layer_slices_and_relays_out_nothing(
        topo, sharded_step, monkeypatch):
    """``zero`` names where each layer's ZeRO-sharded weights leave their
    stack, and changes nothing but names: the step lowered without it is
    the same program.  No collective carries it: the partitioner names a
    weight gather after the dot it feeds (``mlp``, ``proj``) and leaves
    the gradient reduce-scatters unnamed, which is why the benchmark reads
    ZeRO's collectives by kind.  ``pytest -s`` prints them by scope."""
    import collections
    import re
    from repro import tracing
    from repro.analysis.roofline import op_scope

    hlo = sharded_step[0]
    named = [ln for ln in hlo.splitlines()
             if re.search(r'op_name="[^"]*/zero/', ln)]
    assert named
    kinds = ("all-gather", "reduce-scatter", "collective-permute",
             "all-reduce")
    found = collections.Counter()
    for ln in hlo.splitlines():
        m = re.match(r"\s*(ROOT )?%[\w.\-]+ = .*? ([a-z][a-z0-9\-]*)\(", ln)
        kind = m and re.sub(r"-start$", "", m.group(2))
        if kind in kinds:
            found[(kind, op_scope(ln) or "none")] += 1
    print("\nZeRO's collectives by (kind, scope):", dict(found))
    assert found and not any(sc == tracing.ZERO for _, sc in found)
    assert {sc for kind, sc in found if kind == "all-gather"} >= {"mlp",
                                                                   "proj"}

    real = tracing.scope
    monkeypatch.setattr(tracing, "scope", lambda name: (
        contextlib.nullcontext() if name == tracing.ZERO else real(name)))
    without = _lower_sharded_step(topo)[0].as_text()
    monkeypatch.setattr(tracing, "scope", real)
    assert _lower_sharded_step(topo)[0].as_text() == without
