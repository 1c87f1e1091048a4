"""chip_smoke.py's control flow on the CPU, at the SMOKE config: the phase
functions run here with interpret-mode kernels (and, for the four-chip
phase, four simulated devices in a child process), while ``main`` itself
refuses any device that is not a TPU."""
import json
import math
import os
import shutil
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _cpu_env(devices=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (os.path.join(ROOT, "src") + os.pathsep + ROOT
                         + os.pathsep + env.get("PYTHONPATH", ""))
    if devices:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return env


def test_one_chip_phase_on_cpu():
    r = chip_smoke.phase_one_chip(full=False, steps=2, temporal=4,
                                  spatial=16)
    assert len(r["losses"]) == 2 and all(map(math.isfinite, r["losses"]))
    assert len(r["step_seconds"]) == 2
    # interpret mode: the kernel runs, but not as a compiled TPU call
    assert not r["kernel_in_step"]
    assert (abs(r["pallas_loss"] - r["ref_loss"])
            <= chip_smoke.LOSS_RTOL * abs(r["ref_loss"]))


_FOUR = """
import json, chip_smoke
r = chip_smoke.phase_four_chips(full=False, temporal=8, spatial=16)
print(json.dumps(r))
"""


def test_four_chip_phase_on_cpu():
    proc = subprocess.run([sys.executable, "-c", _FOUR], cwd=ROOT,
                          env=_cpu_env(4), capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    r = json.loads(proc.stdout.strip().splitlines()[-1])
    assert len(r["losses"]) == 2 and all(map(math.isfinite, r["losses"]))
    # the CPU contract: one all-to-all per planned forward switch
    assert r["fwd_a2a"] == r["planned_fwd_a2a"] == 2
    assert r["step_a2a"] >= r["planned_fwd_a2a"] + r["planned_bwd_a2a"]
    assert not r["kernel_in_step"]           # interpret mode on the CPU


def test_main_refuses_a_cpu(capsys):
    assert chip_smoke.main([]) == 1
    assert '"ok"' not in capsys.readouterr().out


def test_main_alone_in_a_directory(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = _cpu_env()
    env.pop("PYTHONPATH")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


_CACHE = """
import json, os, jax
from repro.launch import compile_cache
d = compile_cache.enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(5.0)).block_until_ready()
print(json.dumps({"dir": d, "config": jax.config.jax_compilation_cache_dir,
                  "default": compile_cache.DEFAULT_DIR,
                  "files": sorted(os.listdir(d)) if os.path.isdir(d)
                  else []}))
"""


def test_compile_cache_goes_where_the_environment_says(tmp_path):
    env = _cpu_env()
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    proc = subprocess.run([sys.executable, "-c", _CACHE], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    r = json.loads(proc.stdout.strip().splitlines()[-1])
    assert r["dir"] == r["config"] == str(tmp_path)
    assert r["files"]                       # the compile landed there
    assert r["default"] == os.path.join(os.path.abspath(ROOT), ".jax_cache")

    # without the variable: the fixed, git-ignored directory in the checkout
    env.pop("JAX_COMPILATION_CACHE_DIR")
    code = ("import jax; from repro.launch import compile_cache as c; "
            "print(c.enable_compile_cache() == c.DEFAULT_DIR == "
            "jax.config.jax_compilation_cache_dir)")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.stdout.strip().splitlines()[-1] == "True", proc.stderr
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
