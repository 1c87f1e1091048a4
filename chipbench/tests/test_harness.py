"""The benchmark's files name things that exist, and ``run.py`` refuses
to measure anywhere but on the cell's TPU chips."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import harness
from chipbench.harness import Benchmark

ROOT = harness.ROOT
BENCH = Benchmark(ROOT)
SPEC = BENCH.spec
CELLS = [w["name"] for w in SPEC["workloads"]]


def _run(cwd, *args, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "chipbench", "run.py"), *args],
        cwd=cwd, env=e, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_cpu_and_prints_no_result():
    p = _run(ROOT, "--workload", CELLS[0], "--seed", str(2 ** 31 + 7),
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_refuses_a_copy_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for d in SPEC["paths"]:
        shutil.copytree(os.path.join(ROOT, d), tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path), "--workload", CELLS[0], "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_to_files_that_exist(cell):
    w = BENCH.cell(cell)
    config = BENCH.config(w["config"])
    traffic = BENCH.traffic(w["traffic"])
    limits = BENCH.limits(cell)
    assert BENCH.driver(traffic["kind"]).run
    from repro import configs
    assert configs.get(config["arch"]).family == "t2d"
    mesh = traffic["mesh"]
    assert mesh[0] * mesh[1] == w["chips"]
    from chipbench import compare
    assert set(limits) == set(compare.NAMES)
    for m in BENCH.metrics(cell, trace=False):
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in BENCH.metrics(cell, False))
    assert BENCH.metrics(cell, trace=True)


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    m = next(x for x in SPEC["per_layer"] if x["name"] == metric)
    assert callable(BENCH.reader(metric).read)
    e2e = {x["name"] for x in SPEC["end_to_end"]}
    assert m["moves"] in e2e
    assert set(m.get("workloads", [])) <= set(CELLS)


def test_every_file_under_the_benchmark_parses_and_is_used():
    used_configs = {os.path.basename(c["file"]) for c in SPEC["configs"]}
    assert sorted(os.listdir(os.path.join(ROOT, "chipbench", "configs"))) \
        == sorted(used_configs)
    traffics = {w["traffic"] + ".json" for w in SPEC["workloads"]}
    assert set(os.listdir(os.path.join(ROOT, "chipbench", "traffic"))) \
        == traffics
    limits = {w["name"] + ".json" for w in SPEC["workloads"]}
    assert set(os.listdir(os.path.join(ROOT, "chipbench", "limits"))) \
        == limits
    readers = {m["name"] + ".py" for m in SPEC["per_layer"]}
    assert {f for f in os.listdir(os.path.join(ROOT, "chipbench", "metrics"))
            if f.endswith(".py")} == readers
    for sub in ("configs", "traffic", "limits"):
        for f in os.listdir(os.path.join(ROOT, "chipbench", sub)):
            with open(os.path.join(ROOT, "chipbench", sub, f)) as fh:
                json.load(fh)
