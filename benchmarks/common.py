"""Shared benchmark plumbing: subprocess SPMD measurement + CSV output."""
import json
import os
import subprocess
import sys

HERE = os.path.dirname(__file__)
SRC = os.path.join(HERE, "..", "src")


def spmd_measure(devices: int, mode: str, *, batch=2, temporal=8,
                 spatial=32, layers=4, d_model=128, heads=8, d_ff=256,
                 modulate=True, grad=False, time_it=False, reps=3,
                 overlap=None, n_kv_heads=None, sp_outer=None):
    cfg = dict(devices=devices, mode=mode, batch=batch, temporal=temporal,
               spatial=spatial, layers=layers, d_model=d_model, heads=heads,
               d_ff=d_ff, modulate=modulate, grad=grad, time=time_it,
               reps=reps, overlap=overlap, n_kv_heads=n_kv_heads,
               sp_outer=sp_outer)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"     # a CPU simulation, never the chip
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "_spmd_worker.py"),
         json.dumps(cfg)],
        env=env, capture_output=True, text=True, timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError(f"worker failed ({mode}, n={devices}):\n"
                           f"{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def emit(name: str, us_per_call, derived: str):
    print(f"{name},{us_per_call if us_per_call is not None else ''},{derived}")
