"""Fault-tolerance example: train, "lose" the job, restart ELASTICALLY on a
different device count, and continue bit-exact.

Phase 1 trains on 1 device and checkpoints.  Phase 2 (a subprocess with 8
simulated devices) restores the same checkpoint onto a (4, 2) mesh with
ZeRO-sharded parameters and keeps training.  The data pipeline is a pure
function of the step, so the resumed loss curve continues seamlessly.

Run:  PYTHONPATH=src python examples/elastic_restart.py
"""
import json
import os
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "..", "src")

PHASE2 = r"""
import json, sys
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from repro.data.pipeline import DataConfig, make_batch
from repro.models.lm import LMConfig, init_lm, lm_loss
from repro.optim.adamw import OptConfig
from repro.parallel.partition import ParallelPlan, param_pspecs, make_sharder
from repro.train.checkpoint import CheckpointManager
from repro.train.trainer import Trainer, TrainerConfig

ckpt_dir = sys.argv[1]
cfg = LMConfig(name="elastic", n_layers=2, d_model=64, n_heads=4,
               n_kv_heads=2, head_dim=16, d_ff=128, vocab=64,
               dtype=jnp.float32)
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
plan = ParallelPlan(mode="dsp")
sharder = make_sharder(mesh, plan)
params = init_lm(jax.random.PRNGKey(0), cfg)
specs = param_pspecs(params, plan, axis_sizes=dict(mesh.shape))
template = jax.tree_util.tree_map(
    lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                      sharding=NamedSharding(mesh, s)),
    params, specs)

dcfg = DataConfig(task="lm_shift", vocab=64, seq=64, batch=8)
tr = Trainer(loss_fn=lambda p, b: lm_loss(p, b, cfg, sharder=sharder,
                                          backend="ref"),
             params=params,
             opt_cfg=OptConfig(peak_lr=3e-3, warmup_steps=5, total_steps=60),
             cfg=TrainerConfig(total_steps=60, log_every=10, ckpt_every=0),
             data_fn=lambda s: make_batch(dcfg, s), ckpt_dir=ckpt_dir)
mgr = CheckpointManager(ckpt_dir)
step, tree = mgr.restore({"params": template})
tr.params = tree["params"]
tr.start_step = step
print(f"resumed at step {step} on {len(jax.devices())} devices; "
      f"params sharded over mesh {dict(mesh.shape)}")
out = tr.run()
print(json.dumps(out["history"]))
"""


def main():
    with tempfile.TemporaryDirectory() as ckpt:
        # phase 1: single device
        env = dict(os.environ)
        env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
        from repro.data.pipeline import DataConfig, make_batch
        from repro.models.lm import LMConfig, init_lm, lm_loss
        from repro.optim.adamw import OptConfig
        from repro.train.trainer import Trainer, TrainerConfig
        import jax, jax.numpy as jnp

        cfg = LMConfig(name="elastic", n_layers=2, d_model=64, n_heads=4,
                       n_kv_heads=2, head_dim=16, d_ff=128, vocab=64,
                       dtype=jnp.float32)
        dcfg = DataConfig(task="lm_shift", vocab=64, seq=64, batch=8)
        tr = Trainer(loss_fn=lambda p, b: lm_loss(p, b, cfg, backend="ref"),
                     params=init_lm(jax.random.PRNGKey(0), cfg),
                     opt_cfg=OptConfig(peak_lr=3e-3, warmup_steps=5,
                                       total_steps=60),
                     cfg=TrainerConfig(total_steps=30, log_every=10,
                                       ckpt_every=30),
                     data_fn=lambda s: make_batch(dcfg, s), ckpt_dir=ckpt)
        out1 = tr.run()
        print("phase1 (1 device):", out1["history"])

        # phase 2: resume on 8 simulated devices with sharded params
        env["JAX_PLATFORMS"] = "cpu"     # simulated devices, never the chip
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        proc = subprocess.run([sys.executable, "-c", PHASE2, ckpt],
                              env=env, capture_output=True, text=True,
                              timeout=600)
        print(proc.stdout)
        assert proc.returncode == 0, proc.stderr[-2000:]
        hist2 = json.loads(proc.stdout.strip().splitlines()[-1])
        assert hist2[-1][1] < out1["history"][0][1], "loss keeps improving"
        print("OK — elastic restart onto 8 devices continued training")


if __name__ == "__main__":
    main()
