"""Training driver: the launcher's trainer, timed over whole steps.

Set-up builds the ``Trainer`` that ``repro.launch.train.build`` assembles
and installs in it what ``--seed`` decides (``install``): the weights
(``data.init_params``), fresh optimizer state and the batches
(``data.video_batch`` as the trainer's ``data_fn``).  The first
``check_steps`` steps run through the same jitted, donating step and feed
as the window's, and what they leave is read for the comparison and kept
on the host: each step's loss, the first gradient from Adam's first moment
after step 1, and the master weights' change after the last.  They also
compile the step.  The window then runs whole steps
as ``Trainer.run`` does (a batch, the step, ``block_until_ready``) until
``seconds`` have passed.  After the window the program's state is freed
and the float32 reference trains the same weights on the same batches.
"""
from __future__ import annotations

import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, data, reference

OPT_FIELDS = ("peak_lr", "warmup_steps", "total_steps", "min_lr_ratio",
              "b1", "b2", "eps", "weight_decay", "grad_clip")


def _argv(config, traffic, smoke):
    mesh = traffic["mesh"]
    argv = ["--arch", config["arch"], "--batch", str(traffic["batch"]),
            "--temporal", str(traffic["temporal"]),
            "--spatial", str(traffic["spatial"]),
            "--steps", str(traffic["optimizer"]["total_steps"]),
            "--lr", repr(traffic["optimizer"]["peak_lr"])]
    if not smoke:
        argv.append("--full")
    if mesh[0] * mesh[1] > 1:
        argv += ["--mesh", f"{mesh[0]},{mesh[1]}"]
    return argv


def _check_program(cfg, opt_cfg, config, traffic, smoke):
    """The program must run what the configuration and traffic state."""
    want = {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "head_dim": cfg.dh, "d_ff": cfg.d_ff,
            "in_dim": cfg.in_dim, "dtype": jnp.dtype(cfg.dtype).name,
            "mlp": cfg.mlp_kind, "modulate": cfg.modulate,
            "kv_heads": cfg.kvh}
    if not smoke:
        bad = {k: (v, config[k]) for k, v in want.items() if config[k] != v}
        if bad:
            raise ValueError(f"program config differs from the file "
                             f"(program, file): {bad}")
    opt = traffic["optimizer"]
    bad = {k: (getattr(opt_cfg, k), opt[k]) for k in OPT_FIELDS
           if getattr(opt_cfg, k) != opt[k]}
    if bad or not opt_cfg.use_master:
        raise ValueError(f"program optimizer differs from the traffic "
                         f"(program, file): {bad}")


def build(config, traffic, seed, *, smoke=False):
    """The launcher's trainer with what ``seed`` decides installed; returns
    (trainer, program config, params shapes, shardings for ``install``)."""
    from repro import configs
    from repro.launch.train import build as launch_build, parse_args
    spec = configs.get(config["arch"])
    cfg = spec.smoke if smoke else spec.config
    trainer, _ = launch_build(parse_args(_argv(config, traffic, smoke)))
    _check_program(cfg, trainer.opt_cfg, config, traffic, smoke)
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), trainer.params)
    where = jax.tree_util.tree_map(lambda a: a.sharding,
                                   (trainer.params, trainer.opt_state))
    free(trainer)
    install(trainer, seed, shapes, where, traffic, cfg.in_dim)
    return trainer, cfg, shapes, where


def install(trainer, seed, shapes, where, traffic, in_dim):
    """Give the trainer the weights, fresh optimizer state and batches of
    ``seed``, on the shardings ``where`` that its compiled step expects."""
    from repro.optim.adamw import init_opt_state
    params = jax.device_put(data.init_params(seed, shapes), where[0])
    trainer.params = params
    trainer.opt_state = jax.device_put(
        init_opt_state(params, trainer.opt_cfg), where[1])
    shape = dict(batch=traffic["batch"], temporal=traffic["temporal"],
                 spatial=traffic["spatial"], in_dim=in_dim)
    trainer.data_fn = lambda step: data.video_batch(seed, step, **shape)


@jax.jit
def _leaf_sq(tree):
    return [jnp.sum(jnp.square(a.astype(jnp.float32)))
            for a in jax.tree_util.tree_leaves(tree)]


@jax.jit
def _diff(now, first):
    return now.astype(jnp.float32) - first.astype(jnp.float32)


def _by_path(tree, values, scale=1.0):
    return {k: float(v) * scale
            for k, v in zip(data.leaf_paths(tree), values)}


class Window:
    """Counts the programs compiled or loaded from the compile cache while
    it is open: none should be, inside the timed window."""

    def __init__(self):
        self.compiles = 0

    def __call__(self, event, *_, **__):
        if ("backend_compile" in event
                or "cache_retrieval" in event):
            self.compiles += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self)
        return False


def step(trainer, i):
    """One step as ``Trainer.run`` takes it, inside the host spans the
    trace attributes idle time to."""
    with jax.profiler.TraceAnnotation("data"):
        batch = trainer.data_fn(i)
    with jax.profiler.TraceAnnotation("dispatch"):
        trainer.params, trainer.opt_state, metrics = trainer.step_fn(
            trainer.params, trainer.opt_state, batch)
    with jax.profiler.TraceAnnotation("sync"):
        jax.block_until_ready(metrics["loss"])
    return metrics["loss"]


def check_steps(trainer, shapes, seed, n, b1):
    """Run the first ``n`` steps and read what the comparison needs: the
    losses, the first gradient and the weights' change, as squared norms
    by leaf and, in ``grad`` and ``delta``, as leaves on the host."""
    out = {"losses": []}
    for i in range(n):
        out["losses"].append(float(step(trainer, i)))
        if i == 0:
            m = jax.tree_util.tree_leaves(trainer.opt_state["m"])
            out["grad_sq"] = _by_path(shapes, _leaf_sq(m),
                                      1.0 / (1.0 - b1) ** 2)
            out["grad"] = [np.asarray(a) for a in m]
    master = jax.tree_util.tree_leaves(trainer.opt_state["master"])
    out["delta"] = [np.asarray(_diff(leaf, data.init_leaf(seed, shapes, i)))
                    for i, leaf in enumerate(master)]
    out["delta_sq"] = {
        path: float(np.sum(np.square(d, dtype=np.float64)))
        for path, d in zip(data.leaf_paths(shapes), out["delta"])}
    return out


def free(trainer):
    for tree in (trainer.params, trainer.opt_state):
        for a in jax.tree_util.tree_leaves(tree):
            a.delete()
    trainer.params = trainer.opt_state = None
    gc.collect()


def run_reference(model, traffic, seed, shapes, devices, n, *,
                  precision="f32", against=None, keep=False):
    """The reference's (or the control's) reading of the same ``n`` steps.
    With ``against`` (another run's reading) also the ``cos`` gaps to its
    ``grad`` and ``delta``; with ``keep`` its own ``grad`` and ``delta`` on
    the host, for a run compared later."""
    opt = reference.Optimizer(**{k: traffic["optimizer"][k]
                                 for k in OPT_FIELDS})
    ref = reference.Reference(model, opt, seed, shapes,
                              precision=precision, devices=devices)
    shape = dict(batch=traffic["batch"], temporal=traffic["temporal"],
                 spatial=traffic["spatial"], in_dim=model.in_dim)
    leaves = jax.tree_util.tree_leaves(shapes)
    out = {"losses": []}
    sums = {}
    for i in range(n):
        loss, gsq, grads = ref.train_step(data.video_batch(seed, i, **shape))
        out["losses"].append(loss)
        if i == 0:
            out["grad_sq"] = gsq
            kept = {} if keep else None
            sums["grad"] = compare.sums(ref.pieces(*grads),
                                        against and against["grad"], kept)
            if keep:
                out["grad"] = compare.stacked(kept, leaves)
        del grads
    kept = {} if keep else None
    sums["delta"] = compare.sums(ref.delta_pieces(),
                                 against and against["delta"], kept)
    if keep:
        out["delta"] = compare.stacked(kept, leaves)
    out["delta_sq"] = {k: v[0] for k, v in sums["delta"].items()}
    if against is not None:
        out["cos"] = {k: compare.cos_gaps(v) for k, v in sums.items()}
    ref.free()
    return out


def program_model(cfg) -> reference.Model:
    return reference.Model(n_layers=cfg.n_layers, d_model=cfg.d_model,
                           n_heads=cfg.n_heads, head_dim=cfg.dh,
                           d_ff=cfg.d_ff, in_dim=cfg.in_dim)


def run(ctx):
    """Set up, time the window, then check; returns the driver's readings
    (see ``chipbench.run``).  A traced run traces ``trace_steps`` whole
    steps in place of the timed window."""
    traffic, seed = ctx.traffic, ctx.seed
    n_check = traffic["check_steps"]
    trainer, cfg, shapes, _ = build(ctx.config, traffic, seed,
                                    smoke=ctx.smoke)
    ctx.say(f"built: {time.monotonic() - ctx.t_start:.1f} s")
    prog = check_steps(trainer, shapes, seed, n_check,
                       traffic["optimizer"]["b1"])

    tokens = traffic["batch"] * traffic["temporal"] * traffic["spatial"]
    losses = []
    i = n_check
    ctx.say(f"set-up done: {time.monotonic() - ctx.t_start:.1f} s")
    if ctx.trace:
        ctx.start_trace()
    with Window() as window:
        t0 = time.monotonic()
        setup_s = t0 - ctx.t_start
        while True:
            losses.append(step(trainer, i))
            i += 1
            elapsed = time.monotonic() - t0
            if (len(losses) >= traffic["trace_steps"] if ctx.trace
                    else elapsed >= ctx.seconds):
                break
    if ctx.trace:
        ctx.stop_trace()
    losses = [float(x) for x in losses]
    failed = sum(1 for x in losses if not math.isfinite(x))
    out = {
        "attempted": len(losses), "failed": failed,
        "window_compiles": window.compiles,
        "metrics": {"train_tokens_per_s": len(losses) * tokens / elapsed,
                    "setup_s": setup_s},
    }
    ctx.after_window(out)      # memory, trace metrics: program still live
    free(trainer)
    t1 = time.monotonic()
    model = program_model(cfg) if ctx.smoke else reference.Model.from_config(
        ctx.config)
    ref = run_reference(model, traffic, seed, shapes, ctx.devices, n_check,
                        against=prog)
    ctx.say(f"reference: {time.monotonic() - t1:.1f} s")
    out["numbers"] = compare.numbers(prog, ref, ref["cos"])
    return out
