"""The four-chip cell at the SMOKE size, on four simulated CPU devices in
a process of their own (``sp4_worker.py``): the launcher's trainer on the
traffic's (1, 4) mesh, checked against ``chipbench/reference.py`` with the
cell's limits, is correct as it is and not correct with half of the batch
left out."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, os.path.join(HERE, "sp4_worker.py")],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return [json.loads(ln) for ln in p.stdout.splitlines()
            if ln.startswith("{")]


def test_the_sharded_cell_is_correct(results):
    r = results[0]
    assert len(set(r["devices"])) == 4
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["window_compiles"] == 0


def test_the_sharded_cell_with_half_the_batch_is_not_correct(results):
    r = results[1]
    assert not r["correct"], r["checks"]
