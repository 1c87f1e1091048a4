"""Training driver: ``python -m repro.launch.train --arch <id> [...]``.

Runs a training loop through the full stack — config registry, parallel
plan, AdamW, checkpointing, straggler watchdog — on the devices JAX finds:
the TPU chips of the host, or the CPU, where ``--devices N`` simulates N
host devices (it sets XLA_FLAGS before jax initialises).

Smoke-scale by default (the arch's SMOKE config); ``--full`` trains the
published config.  The paper's 720M DiT at full width on one TPU v5e::

    python -m repro.launch.train --arch transformer2d-720m --full \
        --batch 1 --temporal 16 --spatial 256 --steps 3

and sequence-parallel over the four chips of one host with ``--mesh 1,4``.
"""
import argparse
import os


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128,
                    help="sequence length (lm / encdec)")
    ap.add_argument("--temporal", type=int, default=8,
                    help="video frames T (transformer2d)")
    ap.add_argument("--spatial", type=int, default=16,
                    help="tokens per frame S (transformer2d)")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--replan", type=int, default=0,
                    help="elastic resize onto N devices after resume "
                         "(re-solves the plan; lm family)")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--devices", type=int, default=0,
                    help="simulate N host devices (set before jax init)")
    ap.add_argument("--mesh", default=None,
                    help="dp,mp mesh shape over all devices, e.g. 1,4")
    ap.add_argument("--full", action="store_true",
                    help="use the full published config")
    return ap.parse_args(argv)


def build(args):
    """The trainer ``main`` runs, built from parsed arguments: the model at
    the chosen config, its loss, the data stream and — with ``--mesh`` —
    params placed on the mesh and the solved DSP schedule.  Returns
    ``(trainer, loss_fn)``."""
    import jax
    from repro import configs
    from repro.data.pipeline import DataConfig, make_batch
    from repro.optim.adamw import OptConfig
    from repro.parallel.partition import make_sharder
    from repro.train.trainer import (ElasticSpec, Trainer, TrainerConfig,
                                     place_tree)

    spec = configs.get(args.arch)
    cfg = spec.config if args.full else spec.smoke

    mesh = None
    sharder = None
    topology = None
    if args.mesh:
        dp, mp = (int(x) for x in args.mesh.split(","))
        from repro.launch.mesh import make_mesh, mesh_topology
        mesh = make_mesh((dp, mp), ("data", "model"))
        sharder = make_sharder(mesh, spec.plan)
        topology = mesh_topology(mesh, "ici")

    # joint fwd+bwd planned schedule: priced into the run summary (and, for
    # the t2d executor path, executed) when training on a DSP mesh
    schedule = None
    elastic = None
    if spec.family == "lm":
        from repro.models.lm import dsp_schedule, init_lm, lm_loss
        params = init_lm(jax.random.PRNGKey(0), cfg)
        dcfg = DataConfig(task="lm_shift", vocab=cfg.vocab, seq=args.seq,
                          batch=args.batch)
        if mesh is not None and spec.plan.mode == "dsp":
            schedule = dsp_schedule(cfg, mesh.shape.get("model", 1),
                                    seq=args.seq, batch=args.batch,
                                    topology=topology, joint=True)

        def loss_fn(p, b):
            return lm_loss(p, b, cfg, sharder=sharder, backend="ref")

        # --replan support: rebuild the loss and re-solve the schedule on
        # whatever mesh the trainer resizes onto
        def make_loss(m, sh, sched):
            return lambda p, b: lm_loss(p, b, cfg, sharder=sh,
                                        backend="ref")

        def solve_schedule(sp, topo):
            return dsp_schedule(cfg, sp, seq=args.seq, batch=args.batch,
                                topology=topo, joint=True)

        elastic = ElasticSpec(
            make_loss=make_loss,
            solve_schedule=(solve_schedule if spec.plan.mode == "dsp"
                            else None),
            plan=spec.plan)
    elif spec.family == "encdec":
        from repro.models.encdec import init_encdec, encdec_loss
        params = init_encdec(jax.random.PRNGKey(0), cfg)
        dcfg = DataConfig(task="encdec", vocab=cfg.vocab, seq=args.seq // 2,
                          enc_seq=args.seq, batch=args.batch,
                          frontend_dim=cfg.frontend_dim)

        def loss_fn(p, b):
            return encdec_loss(p, b, cfg, sharder=sharder, backend="ref")
    else:
        from repro.models.transformer2d import dsp_schedule, init_t2d, t2d_loss
        params = init_t2d(jax.random.PRNGKey(0), cfg)
        dcfg = DataConfig(task="video", batch=args.batch,
                          temporal=args.temporal, spatial=args.spatial,
                          in_dim=cfg.in_dim)
        psched = None
        if mesh is not None:
            psched = dsp_schedule(cfg, mesh.shape.get("model", 1),
                                  t_len=args.temporal, s_len=args.spatial,
                                  batch=args.batch, topology=topology,
                                  joint=True)
            schedule = psched.schedule

        def loss_fn(p, b):
            # attention forward runs the Pallas flash kernel (compiled on
            # the TPU, interpreted on the CPU)
            return t2d_loss(p, b, cfg, mesh=mesh, schedule=psched)

    if mesh is not None:
        # init leaves params on the default device: put them on the mesh as
        # the plan says (the optimizer state follows their shardings)
        params = place_tree(params, mesh, spec.plan)
    trainer = Trainer(
        loss_fn=loss_fn, params=params,
        opt_cfg=OptConfig(peak_lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                          total_steps=args.steps),
        cfg=TrainerConfig(total_steps=args.steps, grad_accum=args.grad_accum,
                          log_every=max(args.steps // 10, 1),
                          ckpt_every=max(args.steps // 4, 1) if args.ckpt_dir
                          else 0, grad_compress=args.grad_compress),
        data_fn=lambda s: make_batch(dcfg, s),
        ckpt_dir=args.ckpt_dir, schedule=schedule, mesh=mesh,
        topology=topology, elastic=elastic)
    return trainer, loss_fn


def main(argv=None):
    args = parse_args(argv)
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    trainer, _ = build(args)
    if args.resume:
        trainer.try_resume()
    if args.replan:
        trainer.replan(args.replan)
    out = trainer.run()
    print("history:", out["history"])
    print("stragglers:", out["stragglers"])
    if "plan" in out:
        print("planned comm:", out["plan"])
    first = out["history"][0][1] if out["history"] else float("nan")
    last = out["history"][-1][1] if out["history"] else float("nan")
    print(f"loss {first:.4f} -> {last:.4f}")
    return out


if __name__ == "__main__":
    import logging
    logging.basicConfig(level=logging.INFO)
    main()
