"""Multi-device behaviour (8 simulated CPU devices) — each scenario runs in
a fresh subprocess so the main pytest process keeps the 1-device default
(the dry-run instructions forbid setting XLA_FLAGS globally)."""
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(__file__)
SRC = os.path.join(HERE, "..", "src")

SCENARIOS = [
    "dsp_primitives",
    "t2d_modes",
    "lm_parallel_equivalence",
    "decode_sharded",
    "elastic_checkpoint",
    "elastic_train_resize",
    "grad_allreduce_compression",
    "joint_bwd_parity",
    "scan_joint_bwd_parity",
    "continuous_serving_sharded",
    "paged_serving_sharded",
    "layout2d_t2d",
]


@pytest.mark.parametrize("name", SCENARIOS)
def test_scenario(name):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"     # simulated devices, never the chip
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "md_scenarios.py"), name],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, (
        f"scenario {name} failed:\nSTDOUT:\n{proc.stdout}\n"
        f"STDERR:\n{proc.stderr[-4000:]}")
    assert f"{name} OK" in proc.stdout
