"""Subprocess worker for tests/test_hlo_collectives.py.

Runs with XLA_FLAGS=--xla_force_host_platform_device_count=8; compiles

* the transformer2d DSP forward through BOTH executor backends (auto
  constraints under jit, explicit collectives inside shard_map) plus a bare
  ``split``,
* the explicit DSP forward under ``overlap="chunked"|"double_buffer"``:
  every planned switch decomposes into n-1 independent collective-permute
  hops (zero all-to-all), no permute depends on another permute without
  kernel compute between them, and output/grad stay bitwise equal to the
  synchronous executor,
* the scanned t2d TRAIN step (loss + grad) on both backends — the mirrored
  joint plan, the per-leg control case,
* a synthetic scanned executor program (free stages, ``lax.scan``) under a
  mirrored plan and two FORCED non-mirrored joint plans — the per-period
  custom_vjp backward contract, leg by leg,
* the scanned-LM train loss + grad under the mirrored joint plan and a
  forced non-mirrored plan,

and prints one JSON line with the parsed HLO collective counts next to the
planned counts from the schedule executor
(``expected_collectives`` / ``expected_bwd_collectives``), and the counts
of those that carry the ``dsp_switch`` scope.
"""
import json
import sys


def _counts(parse, fn, *args):
    import jax
    txt = jax.jit(fn).lower(*args).compile().as_text()
    st = parse(txt)
    return {k: int(v) for k, v in st.by_kind_count.items()}


def _instructions(lines):
    """(name, opcode, operand-names) per instruction of one computation."""
    import re
    out = []
    for ln in lines:
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*"
                     r"(?:\([^=]*?\)|\S+)\s+([\w\-]+)\((.*)\)", ln)
        if not m:
            continue
        name, op, rest = m.groups()
        # strip shapes/attrs so top-level commas separate operands
        rest = re.sub(r"\[[^\]]*\]|\{[^}]*\}", "", rest)
        operands = []
        for chunk in rest.split(","):
            if "=" in chunk:          # index=0, direction=LT, to_apply=...
                continue
            toks = chunk.split()
            if toks:
                operands.append(toks[-1].lstrip("%"))
        out.append((name, op, operands))
    return out


def _bare_permute_chains(hlo: str) -> int:
    """Collective-permute pairs serialized WITHOUT kernel compute between
    them: walk each permute's operands backwards through data-movement ops
    only (slice / reshape / copy / tuple / ...), stopping at anything
    opaque (fusion, dot, while, parameter, ...).  0 means every
    permute->permute dependency path crosses kernel compute — the
    structural form of "the hops span the kernel" on a backend that lowers
    collectives synchronously (CPU emits no -start/-done pairs to inspect),
    which is what lets the async pipeliner stream shard i+1 while the
    kernel consumes shard i."""
    from repro.analysis.roofline import _split_computations
    stop = {"fusion", "dot", "convolution", "while", "parameter",
            "constant", "iota", "custom-call", "call", "conditional",
            "all-reduce", "all-gather", "all-to-all", "reduce-scatter",
            "reduce", "scatter", "gather", "sort", "rng",
            "rng-bit-generator"}
    bad = 0
    for lines in _split_computations(hlo).values():
        defs = {name: (op, ops) for name, op, ops in _instructions(lines)}
        for name, (op, operands) in defs.items():
            if op not in ("collective-permute", "collective-permute-start"):
                continue
            seen, stack = set(), list(operands)
            while stack:
                nm = stack.pop()
                if nm in seen or nm not in defs:
                    continue
                seen.add(nm)
                kind, ops = defs[nm]
                if kind in ("collective-permute",
                            "collective-permute-start"):
                    bad += 1
                elif kind == "collective-permute-done" or kind not in stop:
                    stack.extend(ops)
    return bad


def main():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.analysis.roofline import op_scope, parse_data_collectives
    from repro.core.layout import from_mesh
    from repro.launch.mesh import make_mesh
    from repro.core.plan import Stage
    from repro.core.schedule import Schedule, ScheduleExecutor
    from repro.models.transformer2d import (T2DConfig, dsp_schedule, forward,
                                            init_t2d, make_spmd_forward,
                                            t2d_loss)

    cfg = T2DConfig(name="hlo", n_layers=4, d_model=64, n_heads=4, d_ff=128,
                    in_dim=16, modulate=False, dtype=jnp.float32)
    b, t, s = 2, 8, 16
    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = from_mesh(mesh)
    params = init_t2d(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (b, t, s, cfg.in_dim))
    tt = jnp.zeros((b,))

    def counts(fn, *args):
        return _counts(parse_data_collectives, fn, *args)

    def switch_counts(fn, *args):
        """Counts of the collectives that carry the ``dsp_switch`` scope."""
        return _counts(lambda txt: parse_data_collectives(
            txt, where=lambda ln: op_scope(ln) == "dsp_switch"), fn, *args)

    def block_anchor_bwd_counts(fn, *args):
        """Counts of the backward's collectives at a block's own sharding
        constraint (a transposed anchor)."""
        def where(ln):
            return ("transpose(" in ln and "/sharding_constraint" in ln
                    and op_scope(ln) in ("spatial", "temporal"))
        return _counts(lambda txt: parse_data_collectives(txt, where=where),
                       fn, *args)

    # ---- forward contract (both backends + split) -------------------------
    psched = dsp_schedule(cfg, mesh.shape["model"], t_len=t, s_len=s, batch=b)
    ex = ScheduleExecutor(psched, backend="explicit")
    planned = ex.expected_collectives(cfg.n_layers // 2)

    auto = counts(lambda p, xx, ttt: forward(p, xx, ttt, cfg, mesh=mesh,
                                             mode="dsp", backend="ref",
                                             remat=False), params, x, tt)
    auto_switch = switch_counts(
        lambda p, xx, ttt: forward(p, xx, ttt, cfg, mesh=mesh, mode="dsp",
                                   backend="ref", remat=False),
        params, x, tt)
    explicit = counts(make_spmd_forward(cfg, mesh, mode="dsp", backend="ref"),
                      params, x, tt)

    from repro.core.dsp import split as dsp_split
    split_fn = jax.shard_map(
        lambda y: dsp_split(y, 1), mesh=mesh,
        in_specs=P(None, None), out_specs=P(None, "model"), check_vma=False)
    split_counts = counts(split_fn, jnp.zeros((4, 8), jnp.float32))

    # ---- overlapped switches (PR 6): decomposed permutes + parity ---------
    n_model = mesh.shape["model"]
    sync_fn = make_spmd_forward(cfg, mesh, mode="dsp", backend="ref")

    def auto_fn(p, xx, ttt):
        return forward(p, xx, ttt, cfg, mesh=mesh, mode="dsp",
                       backend="ref", remat=False)

    y_sync = jax.jit(sync_fn)(params, x, tt)
    y_auto = jax.jit(auto_fn)(params, x, tt)

    def mse(fn):
        def loss(p):
            err = fn(p, x, tt).astype(jnp.float32) - x.astype(jnp.float32)
            return jnp.mean(err ** 2)
        return loss

    g_sync = jax.jit(jax.grad(mse(sync_fn)))(params)

    def bitwise(a, b):
        leaves = jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(lambda u, v: bool((u == v).all()), a, b))
        return all(leaves)

    overlap = {"n_shards": n_model,
               "planned_switches": planned["all-to-all"]}
    for m in ("chunked", "double_buffer"):
        ofn = make_spmd_forward(cfg, mesh, mode="dsp", backend="ref",
                                overlap=m)
        txt = jax.jit(ofn).lower(params, x, tt).compile().as_text()
        st = parse_data_collectives(txt)
        g_ov = jax.jit(jax.grad(mse(ofn)))(params)
        overlap[m] = {
            "counts": {k: int(v) for k, v in st.by_kind_count.items()},
            "serialized_pairs": _bare_permute_chains(txt),
            "fwd_bitwise_vs_explicit": bitwise(jax.jit(ofn)(params, x, tt),
                                               y_sync),
            "fwd_bitwise_vs_auto": bitwise(jax.jit(ofn)(params, x, tt),
                                           y_auto),
            "grad_bitwise_vs_explicit": bitwise(g_ov, g_sync),
        }

    # ---- scanned t2d TRAIN step: per-leg counts, mirrored joint control ---
    batch = {"x": x, "t": None, "target": x}
    jsched = dsp_schedule(cfg, mesh.shape["model"], t_len=t, s_len=s,
                          batch=b, joint=True)
    jex = ScheduleExecutor(jsched, backend="auto", ctx=ctx)

    def auto_loss(p):
        return t2d_loss(p, batch, cfg, mesh=mesh, backend="ref", remat=False,
                        schedule=jsched)[0]

    t2d_train = {
        "planned_fwd": jex.expected_collectives(cfg.n_layers // 2),
        "planned_bwd": jex.expected_bwd_collectives(cfg.n_layers // 2),
        "fwd": counts(auto_loss, params),
        "grad": counts(jax.grad(auto_loss), params),
        "grad_switch": switch_counts(jax.grad(auto_loss), params),
        "grad_block_anchor_bwd": block_anchor_bwd_counts(jax.grad(auto_loss),
                                                         params),
        "mirrored": jsched.schedule.mirrored,
    }

    exp_fwd = make_spmd_forward(cfg, mesh, mode="dsp", backend="ref")

    def exp_loss(p):
        err = (exp_fwd(p, batch["x"], tt).astype(jnp.float32)
               - batch["target"].astype(jnp.float32)) ** 2
        return jnp.mean(err)

    t2d_train["explicit_fwd"] = counts(exp_loss, params)
    t2d_train["explicit_grad"] = counts(jax.grad(exp_loss), params)

    # ---- synthetic scanned executor program: forced non-mirrored legs -----
    N_PERIODS = 3
    free = tuple(Stage(frozenset(), f"s{i}") for i in range(2 * N_PERIODS))

    def scan_case(dims, bwd, initial, final):
        sched = Schedule(free, tuple(dims), initial=initial, final=final,
                         bwd_dims=bwd)
        ps = sched.periodic(2)
        cex = ScheduleExecutor(ps, backend="auto", ctx=ctx)

        def loss(w, xx):
            xx = cex.enter(xx)

            def body(xc, wi):
                xc = cex.anchor(xc, 0)      # stage-0 anchor: well-formed body
                xc = (xc + wi) * 0.5
                xc = cex.boundary(xc, 1)
                xc = xc * 2.0
                xc = cex.wrap(xc)
                return xc, None

            xx, _ = jax.lax.scan(body, xx, w)
            xx = cex.exit(xx)
            return jnp.sum(xx.astype(jnp.float32) ** 2)

        w = jnp.linspace(0.9, 1.1, N_PERIODS)
        xx = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 8, 8))
        return {
            "planned_fwd": cex.expected_collectives(N_PERIODS),
            "planned_bwd": cex.expected_bwd_collectives(N_PERIODS),
            "planned_bwd_last": cex.expected_bwd_collectives(N_PERIODS,
                                                             carry="last"),
            "fwd": counts(loss, w, xx),
            "grad": counts(jax.grad(loss, argnums=(0, 1)), w, xx),
        }

    synthetic = {
        "mirrored": scan_case((1, 2) * N_PERIODS, None, 1, 1),
        "swapped": scan_case((1, 2) * N_PERIODS, (2, 1) * N_PERIODS, 1, 1),
        "parked": scan_case((3,) * (2 * N_PERIODS), (1, 2) * N_PERIODS, 3, 3),
    }

    # ---- scanned-LM train step: planned backward reaches the compiler -----
    from repro.models.lm import (LMConfig, dsp_schedule as lm_schedule,
                                 init_lm, lm_loss)
    from repro.parallel.partition import ParallelPlan, make_sharder

    lcfg = LMConfig(name="hlo", n_layers=4, d_model=64, n_heads=8,
                    n_kv_heads=8, head_dim=8, d_ff=128, vocab=64,
                    dtype=jnp.float32)
    lparams = init_lm(jax.random.PRNGKey(3), lcfg)
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 32), 0, 64)
    lbatch = {"tokens": toks, "labels": toks}
    lplan = ParallelPlan(mode="dsp", shard_vocab=False, zero=False)

    def lm_case(**kw):
        sched = lm_schedule(lcfg, mesh.shape["model"], seq=32, batch=2,
                            joint=True, **kw)
        sharder = make_sharder(mesh, lplan, schedule=sched)

        def loss(p, bb):
            return lm_loss(p, bb, lcfg, sharder=sharder, backend="ref",
                           remat=False)[0]

        return {"fwd": counts(loss, lparams, lbatch),
                "grad": counts(jax.grad(loss), lparams, lbatch),
                "mirrored": sched.mirrored}

    lm_train = {"mirrored": lm_case(),
                "forced": lm_case(bwd_dims=(2, 2, 2))}

    # ---- hybrid (ring x DSP) compiled contract (PR 7) ---------------------
    # The ICI x DCN instance the strategy DP picks hybrid on: 2 hosts x 4
    # devices, T=128 forces the s-axis (4) below full sharding for embedded
    # modes at SPATIAL stages, so only temporal stages go hybrid.
    from repro.core.topology import Topology
    from repro.models.transformer2d import strategy_schedule

    hcfg = T2DConfig(name="hlo-hybrid", n_layers=4, d_model=128, n_heads=8,
                     d_ff=256, in_dim=16, modulate=False, n_kv_heads=4,
                     dtype=jnp.float32)
    hb, ht, hs = 2, 128, 4
    hmesh = make_mesh((2, 4), ("sp_out", "sp_in"))
    hparams = init_t2d(jax.random.PRNGKey(5), hcfg)
    hx = jax.random.normal(jax.random.PRNGKey(6), (hb, ht, hs, hcfg.in_dim))
    htt = jnp.zeros((hb,))

    topo = Topology.multihost(2, 4, placement={2: ("ici",)})
    hsched = strategy_schedule(hcfg, 8, t_len=ht, s_len=hs, batch=hb,
                               initial=1, topology=topo)
    hyb_fwd = make_spmd_forward(hcfg, hmesh, mode="hybrid", backend="ref")
    hybrid = {
        "planned": hsched.schedule.expected_strategy_collectives(8, outer=2),
        "strategies": list(hsched.schedule.strategies),
        "n_periods": hcfg.n_layers // 2,
        "fwd": counts(hyb_fwd, hparams, hx, htt),
    }

    print(json.dumps({
        "planned": planned,
        "auto": auto,
        "auto_switch": auto_switch,
        "explicit": explicit,
        "split": split_counts,
        "n_periods": cfg.n_layers // 2,
        "overlap": overlap,
        "t2d_train": t2d_train,
        "synthetic": synthetic,
        "lm_train": lm_train,
        "hybrid": hybrid,
    }))


if __name__ == "__main__":
    main()
