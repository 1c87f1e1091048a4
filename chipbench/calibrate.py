"""Readings that a cell's limits are set from, in one process on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,...,12 \
        --control-seeds 1,2,3

For each seed: the program's checked steps against the float32 reference,
through the same compiled step the runs use (weights and state are drawn
anew for each seed; the step compiles once).  For each control seed also:
the control (the reference with float8 products) against the reference,
and the fault a one-chip training cell can have, planted in the reference
put in the program's place: half of the batch left out (the loss averaged
over the first half of the frames).  A step that returns its state
unchanged reads 1 by ``compare``'s measure and is not run.

Each reading is one JSON line on standard output.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@contextlib.contextmanager
def half_batch():
    from chipbench import reference
    whole = reference.head_loss

    def half(p, x, target, precision):
        t = x.shape[1] // 2
        return whole(p, x[:, :t], target[:, :t], precision)

    reference.head_loss = half
    try:
        yield
    finally:
        reference.head_loss = whole


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    from chipbench import compare
    from chipbench.drivers import train
    from chipbench.harness import Benchmark
    from chipbench.reference import Model
    bench = Benchmark(ROOT)
    cell = bench.cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("calibrate: needs the cell's TPU chips", file=sys.stderr)
        return 1
    devices = devices[:cell["chips"]]
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    model = Model.from_config(config)
    n = traffic["check_steps"]
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}

    def say(cos, **kw):
        worst = {k: max(v, key=v.get) for k, v in cos.items()}
        print(json.dumps(dict(kw, worst_cos_leaf=worst)), flush=True)

    trainer = None
    for seed in seeds:
        t0 = time.monotonic()
        if trainer is None:
            trainer, _, shapes, where = train.build(config, traffic, seed)
        else:
            train.install(trainer, seed, shapes, where, traffic,
                          model.in_dim)
        prog = train.check_steps(trainer, shapes, seed, n,
                                 traffic["optimizer"]["b1"])
        train.free(trainer)
        t1 = time.monotonic()
        ref = train.run_reference(model, traffic, seed, shapes, devices, n,
                                  against=prog, keep=seed in controls)
        t2 = time.monotonic()
        say(ref["cos"], seed=seed, who="program",
            **compare.numbers(prog, ref, ref["cos"]),
            losses=prog["losses"], ref_losses=ref["losses"],
            program_s=t1 - t0, reference_s=t2 - t1)
        del prog
        if seed not in controls:
            continue
        for who, plant, precision in (
                ("control", contextlib.nullcontext(), "fp8"),
                ("half_batch", half_batch(), "f32")):
            t3 = time.monotonic()
            with plant:
                other = train.run_reference(model, traffic, seed, shapes,
                                            devices, n, precision=precision,
                                            against=ref)
            say(other["cos"], seed=seed, who=who,
                **compare.numbers(other, ref, other["cos"]),
                losses=other["losses"], seconds=time.monotonic() - t3)
    return 0


if __name__ == "__main__":
    sys.exit(main())
