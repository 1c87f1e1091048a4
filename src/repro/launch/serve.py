"""Serving driver: ``python -m repro.launch.serve --arch <id>``.

Loads (or initialises) a model and serves it through the plan-aware
ServingEngine.  ``--devices N --mode dsp`` actually serves SHARDED: the
driver builds the (data x model) mesh, the Topology modelling its links
(``--topology``; ``profile:<path>`` fits a measured fabric via
``Topology.from_profile``), and hands both to the engine, which derives its
(plan, schedule, sharder) triple from them; the KV caches are asserted to
land sequence-sharded on the mesh.

Three serving modes:

* default — the static batch reference path (one lockstep ``generate``);
  ``--replan M`` then exercises the elastic-resize path: the engine
  re-plans onto M devices and serves the same prompts again.
* ``--continuous`` — the continuous-batching scheduler: ``--max-batch``
  recycled slots over the sequence-sharded KV pool, a Poisson arrival
  trace (``--arrival`` = mean inter-arrival seconds; 0 = all at once),
  per-token streaming (``--stream``), and a metrics JSON (TTFT/TPOT/
  queue-wait percentiles, throughput, slot occupancy, the priced fabric)
  printed and optionally written to ``--metrics PATH``.
* ``--paged`` — the paged scheduler on top of the same trace machinery:
  ``--block-size`` KV blocks with ref-counted tables,
  ``--prefix-cache``/``--no-prefix-cache`` radix prefix sharing, and
  ``--prefill-chunk N`` chunked prefill; the metrics JSON additionally
  reports block occupancy and the prefix-cache hit rate.
"""
import argparse
import os

TOPOLOGY_PRESETS = ("ici", "torus", "ici_dcn", "uniform")


def _topology_arg(val: str) -> str:
    if val in TOPOLOGY_PRESETS or val.startswith("profile:"):
        return val
    raise argparse.ArgumentTypeError(
        f"--topology must be one of {TOPOLOGY_PRESETS} or profile:<path>, "
        f"got {val!r}")


def resolve_topology(kind: str, sp: int, *, n_hosts=None):
    """Named preset, or ``profile:<path>`` (``Topology.from_profile``) —
    now shared with the dry-run; the ONE resolver lives in
    ``launch/mesh.py``."""
    from repro.launch.mesh import resolve_topology as _resolve
    return _resolve(kind, sp, n_hosts=n_hosts)


def topology_facts(topo, schedule) -> dict:
    """The fabric facts the metrics JSON records: per-link model
    (``launch.mesh.topology_meta``) + what the planner priced on it."""
    from repro.launch.mesh import topology_meta
    out = topology_meta(topo)
    if topo is not None and schedule is not None:
        out["planned_switches"] = schedule.n_switches()
        out["planned_seconds_per_step"] = schedule.per_device_seconds()
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4,
                    help="request count (static: one batch)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--devices", type=int, default=0,
                    help="simulate N host devices (XLA flag; 0 = leave as-is)")
    ap.add_argument("--mode", default="dsp",
                    choices=["dsp", "tp", "none"],
                    help="model-axis role when serving sharded")
    ap.add_argument("--topology", default="ici", type=_topology_arg,
                    help="link model of the SP axis: preset "
                    f"{TOPOLOGY_PRESETS} or profile:<path> (measured "
                    "all-gather samples; prices the plan in seconds)")
    ap.add_argument("--hosts", type=int, default=None,
                    help="host count for --topology ici_dcn")
    ap.add_argument("--data", type=int, default=1,
                    help="data-parallel axis size (model = devices / data)")
    ap.add_argument("--replan", type=int, default=0,
                    help="after serving, re-plan onto this many devices and "
                    "serve again (elastic resize; static mode)")
    ap.add_argument("--continuous", action="store_true",
                    help="serve through the continuous-batching scheduler")
    ap.add_argument("--paged", action="store_true",
                    help="serve through the paged scheduler (block-pool KV, "
                    "radix prefix cache, chunked prefill)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV block (paged mode)")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="share prompt-prefix KV blocks via the radix tree "
                    "(paged mode)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="prompt tokens per chunked-prefill slice (paged "
                    "mode; default: one slice per prompt)")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="decode slots in the KV pool (continuous/paged)")
    ap.add_argument("--arrival", type=float, default=0.0,
                    help="mean inter-arrival seconds of the Poisson request "
                    "trace (continuous mode; 0 = all arrive at once)")
    ap.add_argument("--stream", action="store_true",
                    help="print every generated token as it is emitted")
    ap.add_argument("--metrics", default=None,
                    help="write the engine metrics JSON here")
    args = ap.parse_args(argv)

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax
    import numpy as np
    from repro import configs
    from repro.models.lm import init_lm
    from repro.parallel.partition import ParallelPlan
    from repro.serving.engine import Request, ServingEngine

    spec = configs.get(args.arch)
    assert spec.family == "lm", "serve driver covers the LM family"
    cfg = spec.smoke
    params = init_lm(jax.random.PRNGKey(0), cfg)
    if args.ckpt_dir:
        from repro.train.checkpoint import CheckpointManager
        mgr = CheckpointManager(args.ckpt_dir)
        step, tree = mgr.restore({"params": params})
        params = tree["params"]
        print(f"restored step {step}")

    n_dev = len(jax.devices())
    mesh = topo = None
    plan = ParallelPlan(mode="none")
    if args.mode != "none" and n_dev > 1:
        from repro.launch.mesh import make_mesh
        if n_dev % args.data:
            raise SystemExit(f"{n_dev} devices not divisible by "
                             f"--data {args.data}")
        mesh = make_mesh((args.data, n_dev // args.data), ("data", "model"))
        topo = resolve_topology(args.topology, mesh.shape["model"],
                                n_hosts=args.hosts)
        plan = ParallelPlan(mode=args.mode)
        print(f"mesh {dict(mesh.shape)}; topology "
              f"{[(a.name, a.size) for a in topo.axes]} "
              f"bottleneck {topo.bottleneck_bandwidth/1e9:.1f} GB/s")

    max_len = args.prompt_len + args.new_tokens
    sp = mesh.shape["model"] if mesh is not None else 1
    max_len += (-max_len) % sp          # sequence-sharded cache divisibility
    eng = ServingEngine(params, cfg, max_len=max_len, mesh=mesh, plan=plan,
                        topology=topo)
    if eng.schedule is not None:
        print(f"planned switches={eng.schedule.n_switches()} "
              f"seconds/step={eng.schedule.per_device_seconds():.3e}")

    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0, cfg.vocab)

    if args.continuous or args.paged:
        from repro.serving.scheduler import (ContinuousScheduler,
                                             PagedScheduler)
        rng = np.random.RandomState(0)
        gaps = (rng.exponential(args.arrival, size=args.batch)
                if args.arrival > 0 else np.zeros(args.batch))
        arrivals = np.cumsum(gaps)
        reqs = [Request(prompt=prompts[i], max_new_tokens=args.new_tokens,
                        arrival_time=float(arrivals[i]), request_id=i)
                for i in range(args.batch)]
        stream = None
        if args.stream:
            def stream(req, tok):
                print(f"req{req.request_id} += {tok}", flush=True)
        if args.paged:
            sched = PagedScheduler(eng, max_batch=args.max_batch,
                                   block_size=args.block_size,
                                   prefix_cache=args.prefix_cache,
                                   prefill_chunk=args.prefill_chunk)
        else:
            sched = ContinuousScheduler(eng, max_batch=args.max_batch)
        sched.run(reqs, stream=stream)
        if eng.mesh is not None:
            sched.pool.assert_on_mesh()
            print(f"KV pool sequence-sharded over {eng.sp_degree}-way "
                  f"model axis: OK")
        sched.metrics.extra.update(topology_facts(topo, eng.schedule))
        sched.metrics.extra["n_devices"] = n_dev
        sched.metrics.extra["mode"] = plan.mode
        print(sched.metrics.to_json(args.metrics))
        if args.paged:
            s = sched.metrics.summary()
            hit = s["prefix_hit_rate"]
            print(f"paged: {sched.pool.n_blocks - 1} blocks x "
                  f"{sched.pool.block_size} tokens, peak in use "
                  f"{s['peak_blocks_in_use']}, prefix hit rate "
                  f"{'-' if hit is None else f'{hit:.0%}'}, "
                  f"{s['prefill_chunk_steps']} prefill chunks")
        for r in reqs:
            print(f"req{r.request_id} [{r.result.finish_reason}] "
                  f"ttft={r.result.metrics.ttft:.3f}s: {r.generated}")
        return reqs

    def run(tag):
        # check_sharding asserts the KV caches of the ONE prefill generate
        # runs landed sequence-sharded on the mesh
        out = eng.generate(prompts, max_new_tokens=args.new_tokens,
                           check_sharding=True)
        if eng.mesh is not None:
            print(f"{tag}: KV caches sequence-sharded over "
                  f"{eng.sp_degree}-way model axis: OK")
        for i in range(args.batch):
            print(f"{tag} req{i}: prompt={prompts[i].tolist()[:8]}... "
                  f"generated={out[i].tolist()}")
        return out

    out = run(f"serve[{n_dev}dev]")
    if args.metrics:
        import json
        with open(args.metrics, "w") as f:
            json.dump({"mode": plan.mode, "n_devices": n_dev,
                       **topology_facts(topo, eng.schedule)}, f, indent=2)
    if args.replan:
        eng.replan(args.replan)
        out2 = run(f"replan[{args.replan}dev]")
        same = bool(np.array_equal(np.asarray(out), np.asarray(out2)))
        print(f"replan output identical: {same}")
    return out


if __name__ == "__main__":
    main()
