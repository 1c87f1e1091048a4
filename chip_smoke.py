"""Smoke run of the paper's DiT training step on a TPU.

Trains the full-width transformer2d-720m (28 layer pairs, d_model 1152,
16 heads, bf16; random weights from a fixed seed) through the trainer that
``python -m repro.launch.train`` builds, and checks what comes out.  The
times it prints are from a smoke run, not a benchmark.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # sequence parallel over four chips

One chip (the default): 3 training steps at B=1, T=16, S=256 with finite
losses; the compiled step holds the Pallas kernel (``tpu_custom_call``); the forward loss with the Pallas kernel agrees with
the jnp reference (``backend="ref"``) within ``LOSS_RTOL``.

``--chips 4`` runs only the sequence-parallel phase: a (data=1, model=4)
mesh with the planned DSP schedule, whose compiled step holds the kernel
too; the sharded forward loss agrees with
the unsharded one on chip 0 within ``LOSS_RTOL``; 2 sharded training steps
with finite losses; params and optimizer state spread over all four chips;
and the compiled all-to-all counts printed next to the planned ones.

The forward comparisons run at params whose adaLN modulation is drawn at
random (``_live``): the model's adaLN-zero init makes every block the
identity, where attention could not change the loss.

Everything runs in this one process, since a chip belongs to the first
process that touches JAX.  Without a TPU, or away from the repository's
``src/``, the script exits non-zero and prints no result.  Otherwise the
last line of standard output is one JSON object naming the device.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

ARCH = "transformer2d-720m"
SHAPE = {"batch": 1, "temporal": 16, "spatial": 256}
# Relative gap allowed between two forward losses of the bf16 model that
# differ only in how attention is computed (kernel vs jnp, one chip vs a
# sharded mesh): a few bf16 roundings (eps 2**-8 = 3.9e-3) of activations
# averaged over B*T*S*in_dim squared errors.
LOSS_RTOL = 1e-2


def _check(ok, what):
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def _say(msg):
    print(f"smoke: {msg}", flush=True)


def _build(*, full, batch, temporal, spatial, steps, mesh=None):
    """The launcher's own trainer and loss for these arguments."""
    from repro import configs
    from repro.launch.train import build, parse_args
    argv = ["--arch", ARCH, "--batch", str(batch),
            "--temporal", str(temporal), "--spatial", str(spatial),
            "--steps", str(steps)]
    if full:
        argv.append("--full")
    if mesh is not None:
        argv += ["--mesh", mesh]
    trainer, loss_fn = build(parse_args(argv))
    spec = configs.get(ARCH)
    return trainer, loss_fn, spec.config if full else spec.smoke


def _rel(a, b):
    return abs(a - b) / max(abs(b), 1e-30)


def _compile_step(trainer, batch):
    """AOT-compile the trainer's step (jit reuses the executable) and
    return (compiled, seconds)."""
    t0 = time.perf_counter()
    compiled = trainer.step_fn.lower(trainer.params, trainer.opt_state,
                                     batch).compile()
    return compiled, time.perf_counter() - t0


def _live(params, seed=1):
    """``params`` with every adaLN modulation leaf drawn from ``seed``
    (scale 0.02), each on its leaf's devices: adaLN-zero starts each block
    as the identity, and attention must reach the loss to be compared."""
    import jax
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    out = []
    for (path, x), k in zip(flat, keys):
        if "'mod'" in jax.tree_util.keystr(path):
            x = jax.device_put(0.02 * jax.random.normal(k, x.shape, x.dtype),
                               x.sharding)
        out.append(x)
    return jax.tree_util.tree_unflatten(treedef, out)


def _train(trainer):
    """Run the trainer; every logged loss must be finite."""
    import math
    out = trainer.run()
    losses = [loss for _, loss in out["history"]]
    _check(len(losses) == trainer.cfg.total_steps,
           f"{len(losses)} losses for {trainer.cfg.total_steps} steps")
    _check(all(math.isfinite(v) for v in losses), f"losses {losses}")
    return losses, out["step_seconds"]


def phase_one_chip(*, full=True, steps=3, **shape):
    """Train on one device and compare the Pallas forward with the jnp
    reference.  Returns what it measured; raises on a failed check."""
    import jax
    from repro.models.transformer2d import t2d_loss
    shape = {**SHAPE, **shape}
    trainer, loss_fn, cfg = _build(full=full, steps=steps, **shape)
    batch = trainer.data_fn(0)

    live = _live(trainer.params)
    pallas = float(jax.jit(loss_fn)(live, batch)[0])
    ref = float(jax.jit(lambda p, b: t2d_loss(p, b, cfg, backend="ref"))(
        live, batch)[0])
    del live
    rel = _rel(pallas, ref)
    _say(f"forward loss pallas {pallas!r} ref {ref!r} rel diff {rel!r} "
         f"(limit {LOSS_RTOL})")
    _check(rel <= LOSS_RTOL, f"pallas vs ref forward loss rel diff {rel}")

    compiled, compile_s = _compile_step(trainer, batch)
    kernel = "tpu_custom_call" in compiled.as_text()
    _say(f"train step compiled in {compile_s!r} s; Pallas kernel in the "
         f"compiled step: {kernel}")
    losses, step_s = _train(trainer)
    _say(f"losses {losses}")
    _say(f"step seconds {step_s}")
    return {"losses": losses, "step_seconds": step_s,
            "compile_seconds": compile_s, "kernel_in_step": kernel,
            "pallas_loss": pallas, "ref_loss": ref}


def _bytes_by_device(tree):
    import jax
    per = {}
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        total += leaf.nbytes
        for sh in leaf.addressable_shards:
            per[sh.device] = per.get(sh.device, 0) + sh.data.nbytes
    return per, total


def _check_spread(tree, n, what):
    """Every device of the mesh holds shards, and device 0 not all."""
    import jax
    per, total = _bytes_by_device(tree)
    dev0 = jax.devices()[0]
    _say(f"{what}: {total} bytes; per device "
         f"{[per.get(d, 0) for d in jax.devices()[:n]]}")
    _check(len(per) == n, f"{what} on {len(per)} of {n} devices")
    _check(per.get(dev0, 0) < total, f"device 0 holds all of {what}")


def phase_four_chips(*, full=True, steps=2, n=4, **shape):
    """Sequence-parallel training on a (data=1, model=n) mesh, compared
    with the unsharded forward on device 0."""
    import jax
    from repro.analysis.roofline import parse_data_collectives
    from repro.core.layout import from_mesh
    from repro.core.schedule import ScheduleExecutor
    from repro.models.transformer2d import t2d_loss
    _check(jax.device_count() >= n,
           f"{n} devices wanted, {jax.device_count()} found")
    shape = {**SHAPE, **shape}
    trainer, loss_fn, cfg = _build(full=full, steps=steps, mesh=f"1,{n}",
                                   **shape)
    mesh = trainer.mesh
    pairs = cfg.n_layers // 2
    # the schedule the launcher solved, in its scanned (one layer pair) view
    ex = ScheduleExecutor(trainer.schedule.periodic(2), backend="auto",
                          ctx=from_mesh(mesh))
    planned_fwd = ex.expected_collectives(pairs).get("all-to-all", 0)
    planned_bwd = ex.expected_bwd_collectives(pairs).get("all-to-all", 0)
    _check_spread(trainer.params, n, "params")
    _check_spread(trainer.opt_state, n, "optimizer state")

    batch = trainer.data_fn(0)
    live = _live(trainer.params)
    fwd = jax.jit(loss_fn).lower(live, batch).compile()
    sharded = float(fwd(live, batch)[0])
    one = jax.device_put((live, batch), jax.devices()[0])
    del live
    unsharded = float(jax.jit(lambda p, b: t2d_loss(p, b, cfg))(*one)[0])
    del one
    rel = _rel(sharded, unsharded)
    _say(f"forward loss sharded {sharded!r} unsharded {unsharded!r} rel "
         f"diff {rel!r} (limit {LOSS_RTOL})")
    _check(rel <= LOSS_RTOL, f"sharded vs unsharded loss rel diff {rel}")
    fwd_a2a = parse_data_collectives(fwd.as_text()).by_kind_count.get(
        "all-to-all", 0)

    compiled, compile_s = _compile_step(trainer, batch)
    hlo = compiled.as_text()
    kernel = "tpu_custom_call" in hlo
    step_a2a = parse_data_collectives(hlo).by_kind_count.get("all-to-all", 0)
    _say(f"all-to-alls over {pairs} layer pairs: forward compiled "
         f"{fwd_a2a}, planned {planned_fwd}; train step compiled "
         f"{step_a2a}, planned forward + backward "
         f"{planned_fwd + planned_bwd} (remat recomputes the forward's)")
    _say(f"train step compiled in {compile_s!r} s; Pallas kernel in the "
         f"compiled step: {kernel}")
    losses, step_s = _train(trainer)
    _say(f"losses {losses}")
    _say(f"step seconds {step_s}")
    _check_spread(trainer.params, n, "params after training")
    _check_spread(trainer.opt_state, n, "optimizer state after training")
    return {"losses": losses, "step_seconds": step_s,
            "compile_seconds": compile_s, "kernel_in_step": kernel,
            "sharded_loss": sharded,
            "unsharded_loss": unsharded, "fwd_a2a": fwd_a2a,
            "step_a2a": step_a2a, "planned_fwd_a2a": planned_fwd,
            "planned_bwd_a2a": planned_bwd}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sequence-parallel phase")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {platform}",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    kind = devices[0].device_kind
    _say(f"smoke run, not a benchmark: {kind} x{len(devices)}, "
         f"compile cache {cache}")
    result = phase_four_chips() if args.chips == 4 else phase_one_chip()
    _check(result["kernel_in_step"], "no tpu_custom_call in the compiled step")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
