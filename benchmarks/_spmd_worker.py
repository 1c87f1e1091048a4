"""Subprocess worker: compiles the 2D transformer under a given SP method on
N simulated devices and reports HLO-derived communication volume, collective
counts, memory analysis, and (optional) wall time per step.

Invoked by the benchmark drivers with
XLA_FLAGS=--xla_force_host_platform_device_count=<N>; prints one JSON line.
"""
import json
import sys
import time


def main():
    cfg_json = json.loads(sys.argv[1])
    import jax
    import jax.numpy as jnp
    from repro.analysis.roofline import parse_collectives
    from repro.models.transformer2d import (T2DConfig, init_t2d,
                                            make_spmd_forward, t2d_loss,
                                            forward)

    n = cfg_json["devices"]
    mode = cfg_json["mode"]
    b, t, s = cfg_json["batch"], cfg_json["temporal"], cfg_json["spatial"]
    cfg = T2DConfig(name="bench", n_layers=cfg_json.get("layers", 4),
                    d_model=cfg_json.get("d_model", 128),
                    n_heads=cfg_json.get("heads", 8),
                    d_ff=cfg_json.get("d_ff", 256),
                    in_dim=cfg_json.get("in_dim", 16),
                    modulate=cfg_json.get("modulate", True),
                    n_kv_heads=cfg_json.get("n_kv_heads"),
                    dtype=jnp.float32)
    from repro.launch.mesh import make_mesh
    if mode in ("hybrid", "layout2d"):
        # 2D SP process grid (outer DCN factor major) — launch.mesh
        outer = cfg_json.get("sp_outer") or 2
        mesh = make_mesh((outer, n // outer), ("sp_out", "sp_in"))
    else:
        mesh = make_mesh((n,), ("model",))

    params = init_t2d(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (b, t, s, cfg.in_dim))
    tt = jax.random.uniform(jax.random.PRNGKey(2), (b,))

    overlap = cfg_json.get("overlap")    # dsp only: decomposed switches
    if mode == "layout2d":
        # first-class 2D layouts: the planned Schedule2D drives forward2d
        # on the ("sp_out", "sp_in") grid — per-axis sub-mesh switches
        from repro.models.transformer2d import forward2d
        fn = jax.jit(lambda p, xx, t_: forward2d(p, xx, t_, cfg, mesh=mesh,
                                                 remat=False))
    elif cfg_json.get("grad"):
        fwd = make_spmd_forward(cfg, mesh, mode=mode, backend="ref",
                                remat=True, overlap=overlap)

        def step(p, x, tt):
            def loss(p):
                out = fwd(p, x, tt)
                return jnp.mean(out.astype(jnp.float32) ** 2)
            return jax.grad(loss)(p)
        fn = jax.jit(step)
    else:
        fn = jax.jit(make_spmd_forward(cfg, mesh, mode=mode, backend="ref",
                                       overlap=overlap))

    lowered = fn.lower(params, x, tt)
    compiled = lowered.compile()
    stats = parse_collectives(compiled.as_text())
    mem = compiled.memory_analysis()

    out = {
        "mode": mode, "devices": n,
        "collective_bytes_per_dev": stats.bytes_per_device,
        "collective_count": stats.count,
        "by_kind": stats.by_kind,
        "by_kind_count": stats.by_kind_count,
        "temp_bytes": mem.temp_size_in_bytes,
        "arg_bytes": mem.argument_size_in_bytes,
    }
    if cfg_json.get("time"):
        r = fn(params, x, tt)
        jax.block_until_ready(r)
        t0 = time.monotonic()
        reps = cfg_json.get("reps", 3)
        for _ in range(reps):
            r = fn(params, x, tt)
        jax.block_until_ready(r)
        out["us_per_call"] = (time.monotonic() - t0) / reps * 1e6
    print(json.dumps(out))


if __name__ == "__main__":
    main()
