"""Plan-aware checkpoint subsystem tier (train/checkpoint.py).

Three layers of pinning:

* Round-trip properties — random pytrees (fp32/int32/int8/bf16) survive
  save -> restore bit-identical leaf-for-leaf, including through HAND-SPLIT
  shard layouts (the manifest's merge-along-recorded-dim path — restoring
  under a different sharding than the save is the elastic contract; the
  real-mesh version runs in tests/md_scenarios.py, this process stays on
  the 1-device default).  Leaf-set and global-shape mismatches raise
  loudly; silent zero-fill is the failure mode these exist to forbid.

* Crash injection — a writer SIGKILLed between the shard writes and the
  atomic publish, and an ``os.replace`` that raises, must both leave the
  previous step restorable and their staging dirs garbage-collected by the
  next save; two managers on one directory must not corrupt each other
  (keep-last-k pruning vs in-flight save).

* Ordering regression — ``save`` must ``wait()`` for the in-flight save
  BEFORE snapshotting, not after (the bug: two saves sharing
  ``self._thread`` could interleave).
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.train.checkpoint as C
from repro.core.plan import JointPlan, StrategyPlan, plan_from_dict
from repro.core.topology import Topology
from repro.train.checkpoint import CheckpointManager

HERE = os.path.dirname(__file__)
SRC = os.path.join(HERE, "..", "src")

DTYPES = ("float32", "int32", "int8", "bfloat16")


def _np_dtype(name):
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def _rand_array(rng, shape, dtype_name):
    dt = _np_dtype(dtype_name)
    if dt.kind in "iu":
        lo, hi = (-100, 100) if dt.itemsize > 1 else (-128, 127)
        return rng.integers(lo, hi, size=shape).astype(dt)
    return rng.standard_normal(shape).astype(np.float32).astype(dt)


def _bit_equal(tree_a, tree_b):
    la = jax.tree_util.tree_leaves(tree_a)
    lb = jax.tree_util.tree_leaves(tree_b)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype, (a.dtype, b.dtype)
        assert a.shape == b.shape
        assert a.tobytes() == b.tobytes()


def _template(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype),
        tree)


def _hand_split(ckpt_dir, step, rng):
    """Rewrite a saved step's single-shard leaves as MULTI-shard layouts
    (uneven split along a random eligible dim) — the on-disk shape a
    different (mesh size, plan) would have produced; restore must merge
    them back along the recorded dim."""
    base = os.path.join(ckpt_dir, f"step_{step:08d}")
    with open(os.path.join(base, "manifest.json")) as f:
        man = json.load(f)
    for rec in man["leaves"]:
        shape = tuple(rec["shape"])
        dims = [i for i, d in enumerate(shape) if d >= 2]
        if not dims or len(rec["shards"]) != 1:
            continue
        dim = dims[rng.integers(0, len(dims))]
        cut = int(rng.integers(1, shape[dim]))
        src = rec["shards"][0]
        arr = np.load(os.path.join(base, src["file"]), allow_pickle=False)
        pieces, shards = np.split(arr, [cut], axis=dim), []
        for j, (piece, (lo, hi)) in enumerate(
                zip(pieces, [(0, cut), (cut, shape[dim])])):
            fname = src["file"].replace(".npy", f".split{j}.npy")
            np.save(os.path.join(base, fname), piece, allow_pickle=False)
            index = [list(ix) for ix in src["index"]]
            index[dim] = [lo, hi]
            shards.append({"file": fname, "index": index})
        os.remove(os.path.join(base, src["file"]))
        rec["shards"] = shards
    with open(os.path.join(base, "manifest.json"), "w") as f:
        json.dump(man, f)


def _roundtrip_case(tmpdir, seed):
    rng = np.random.default_rng(seed)
    tree = {}
    for i in range(int(rng.integers(1, 6))):
        rank = int(rng.integers(0, 4))
        shape = tuple(int(rng.integers(1, 6)) for _ in range(rank))
        tree[f"leaf{i}"] = _rand_array(rng, shape,
                                       DTYPES[rng.integers(0, len(DTYPES))])
    d = os.path.join(tmpdir, f"ck{seed}")
    mgr = CheckpointManager(d, async_save=False)
    mgr.save(1, tree, blocking=True)
    _, direct = mgr.restore(_template(tree))
    _bit_equal(tree, direct)
    _hand_split(d, 1, rng)
    _, merged = mgr.restore(_template(tree))
    _bit_equal(tree, merged)


def test_roundtrip_seeded(tmp_path):
    """Deterministic round-trip sweep (runs everywhere; the hypothesis
    variant below widens the search when the dependency is present)."""
    for seed in range(20):
        _roundtrip_case(str(tmp_path), seed)


try:
    import hypothesis.strategies as st
    from hypothesis import given, settings

    @settings(max_examples=30, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_roundtrip_property(tmp_path_factory, seed):
        _roundtrip_case(str(tmp_path_factory.mktemp("hyp")), seed)
except ImportError:
    pass


def test_extreme_dtypes_never_round_through_float(tmp_path):
    """bf16 NaN payloads and full int8 range are bit-preserved — a float64
    bounce would canonicalise/clip them."""
    bf16 = _np_dtype("bfloat16")
    funky = np.array([0x7FC1, 0x0001, 0x8000, 0x3F80], np.uint16).view(bf16)
    tree = {"w": funky, "q": np.arange(-128, 128, dtype=np.int8)}
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, tree, blocking=True)
    _, out = mgr.restore(_template(tree))
    _bit_equal(tree, out)


def test_restore_errors_loudly(tmp_path):
    tree = {"a": np.ones((4, 4), np.float32), "b": np.zeros(3, np.int32)}
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, tree, blocking=True)

    # template key absent from the checkpoint: no silent zero-fill
    with pytest.raises(ValueError, match="missing leaves"):
        mgr.restore({"a": tree["a"], "zzz": tree["b"]})
    # global-shape mismatch
    with pytest.raises(ValueError, match="global shape"):
        mgr.restore({"a": np.ones((4, 5), np.float32)})
    # checkpoint-only keys are fine: sub-tree restore is the params-only path
    _, sub = mgr.restore({"a": _template(tree)["a"]})
    _bit_equal({"a": tree["a"]}, sub)

    # incomplete shard coverage (lost shard record) errors, never zero-fills
    base = os.path.join(str(tmp_path), "step_00000001")
    with open(os.path.join(base, "manifest.json")) as f:
        man = json.load(f)
    rec = next(r for r in man["leaves"] if r["key"] == "a")
    rec["shards"][0]["index"] = [[0, 2], [0, 4]]     # claims half the rows
    with open(os.path.join(base, "manifest.json"), "w") as f:
        json.dump(man, f)
    with pytest.raises(ValueError):
        mgr.restore({"a": tree["a"]})


def test_manifest_records_plan_and_topology(tmp_path):
    plan = JointPlan((1, 2, 1), (2, 2, 1))
    topo = Topology.from_profile(
        4, [(2**20, 1e-4), (2**22, 3e-4), (2**24, 1.1e-3)])
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(5, {"w": np.ones(4, np.float32)}, blocking=True,
             plan=plan, topology=topo, meta={"initial": 1})
    step, man = mgr.load_manifest()
    assert step == 5 and man["format"] == C.FORMAT
    assert plan_from_dict(man["plan"]) == plan
    assert Topology.from_dict(man["topology"]) == topo      # fitted fabric
    assert man["meta"] == {"initial": 1}
    sp = StrategyPlan((1, 2), ("dsp", "ring"))
    assert plan_from_dict(sp.to_dict()) == sp
    assert plan_from_dict({"kind": "dims", "dims": [1, 2]}) == [1, 2]


def test_restore_with_mesh_and_plan(tmp_path):
    """restore(mesh=, plan=) re-derives placements from param_pspecs — the
    restore-onto-a-newly-solved-plan entry point (full resharding runs in
    the md scenarios; here the 1-device mesh pins the API contract)."""
    from repro.launch.mesh import make_mesh
    from repro.parallel.partition import ParallelPlan
    tree = {"embed": {"table": np.ones((8, 4), np.float32)}}
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, tree, blocking=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    _, out = mgr.restore(_template(tree), mesh=mesh,
                         plan=ParallelPlan(mode="dsp"))
    _bit_equal(tree, out)
    assert out["embed"]["table"].sharding.mesh is mesh


# ---------------------------------------------------------------------------
# Crash injection
# ---------------------------------------------------------------------------

_KILL_SCRIPT = """
import os, signal, sys
import jax.numpy as jnp
import repro.train.checkpoint as C

d = sys.argv[1]
tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
m = C.CheckpointManager(d, async_save=False)
m.save(1, tree, blocking=True)

def kill_replace(a, b):            # between the shard writes and the rename
    os.kill(os.getpid(), signal.SIGKILL)
C.os.replace = kill_replace
m.save(2, tree, blocking=True)
"""


def test_sigkill_between_write_and_rename(tmp_path):
    d = str(tmp_path / "ck")
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", _KILL_SCRIPT, d],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == -signal.SIGKILL, proc.stderr[-2000:]

    mgr = CheckpointManager(d, async_save=False)
    # the previous step is still the durable latest and restores intact
    assert mgr.latest() == 1
    want = np.arange(64, dtype=np.float32).reshape(8, 8)
    _, tree = mgr.restore({"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)})
    assert np.asarray(tree["w"]).tobytes() == want.tobytes()
    # the killed writer left its staging dir behind ...
    orphans = [n for n in os.listdir(d) if n.startswith("tmp.")]
    assert orphans, os.listdir(d)
    # ... and the next save garbage-collects it (dead pid)
    mgr.save(3, {"w": want}, blocking=True)
    assert [n for n in os.listdir(d) if n.startswith("tmp.")] == []
    assert mgr.all_steps() == [1, 3]


def test_raising_replace_keeps_previous_step(tmp_path, monkeypatch):
    d = str(tmp_path)
    tree = {"w": np.full((4,), 7.0, np.float32)}
    mgr = CheckpointManager(d, async_save=False)
    mgr.save(1, tree, blocking=True)

    def boom(a, b):
        raise OSError("disk on fire")
    monkeypatch.setattr(C.os, "replace", boom)
    with pytest.raises(OSError, match="disk on fire"):
        mgr.save(2, tree, blocking=True)
    monkeypatch.undo()

    assert mgr.latest() == 1
    _, out = mgr.restore(_template(tree))
    _bit_equal(tree, out)
    assert [n for n in os.listdir(d) if n.startswith("tmp.")]   # orphaned
    mgr.save(3, tree, blocking=True)                            # ... GC'd
    assert [n for n in os.listdir(d) if n.startswith("tmp.")] == []
    assert mgr.all_steps() == [1, 3]


def test_async_failure_surfaces_on_wait(tmp_path, monkeypatch):
    mgr = CheckpointManager(str(tmp_path), async_save=True)

    def boom(a, b):
        raise OSError("late failure")
    monkeypatch.setattr(C.os, "replace", boom)
    mgr.save(1, {"w": np.ones(2, np.float32)})
    with pytest.raises(OSError, match="late failure"):
        mgr.wait()


def test_two_managers_one_dir(tmp_path, monkeypatch):
    """keep-last-k pruning by manager B must not corrupt manager A's
    in-flight save: A's staging dir is registered live, B's GC skips it,
    and both steps publish intact."""
    d = str(tmp_path)
    tree_a = {"w": np.full((64, 64), 1.0, np.float32)}
    tree_b = {"w": np.full((64, 64), 2.0, np.float32)}

    started = threading.Event()
    real_dump = json.dump

    def slow_dump(obj, fp, **kw):    # manifest is written last: delaying it
        if isinstance(obj, dict) and obj.get("step") == 1:
            started.set()            # holds A's save in flight
            time.sleep(0.5)
        return real_dump(obj, fp, **kw)
    monkeypatch.setattr(C.json, "dump", slow_dump)

    a = CheckpointManager(d, keep=3, async_save=True)
    b = CheckpointManager(d, keep=1, async_save=False)
    a.save(1, tree_a)
    assert started.wait(timeout=30)
    for s in (2, 3, 4):              # B saves + prunes while A is in flight
        b.save(s, tree_b, blocking=True)
    a.wait()

    assert a.all_steps() == [1, 4]   # B kept its last, A's landed intact
    _, out1 = a.restore(_template(tree_a), 1)
    _bit_equal(tree_a, out1)
    _, out4 = a.restore(_template(tree_b), 4)
    _bit_equal(tree_b, out4)
    monkeypatch.undo()
    a.save(5, tree_a, blocking=True)
    assert [n for n in os.listdir(d) if n.startswith("tmp.")] == []


def test_save_waits_before_snapshot(tmp_path, monkeypatch):
    """Regression for the save ordering bug: the host snapshot of save N
    must happen AFTER the in-flight save N-1 finishes (wait first), so the
    event order is strictly snapshot/publish alternating — the buggy order
    (flatten before wait) interleaves the two snapshots."""
    events = []
    real_flatten = C._flatten
    real_replace = os.replace

    def log_flatten(tree):
        events.append("flatten")
        return real_flatten(tree)

    def slow_replace(a, b):          # the slow fake writer
        time.sleep(0.3)
        events.append("publish")
        return real_replace(a, b)

    monkeypatch.setattr(C, "_flatten", log_flatten)
    monkeypatch.setattr(C.os, "replace", slow_replace)
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    tree = {"w": np.ones(4, np.float32)}
    mgr.save(1, tree)
    mgr.save(2, tree)
    mgr.wait()
    assert events == ["flatten", "publish", "flatten", "publish"], events


# ---------------------------------------------------------------------------
# inspect_ckpt smoke
# ---------------------------------------------------------------------------

def test_inspect_ckpt_json_schema(tmp_path):
    d = str(tmp_path)
    mgr = CheckpointManager(d, async_save=False)
    mgr.save(2, {"a": np.ones((4, 2), np.float32),
                 "b": np.zeros(3, np.int8)},
             blocking=True, plan=[1, 2, 1],
             topology=Topology.flat_ici(4))
    tool = os.path.join(HERE, "..", "tools", "inspect_ckpt.py")
    proc = subprocess.run([sys.executable, tool, d, "--json"],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    info = json.loads(proc.stdout)
    assert info["step"] == 2 and info["format"] == C.FORMAT
    assert info["n_leaves"] == 2 and info["steps"] == [2]
    assert {l["key"] for l in info["leaves"]} == {"a", "b"}
    assert all(set(l) >= {"shape", "dtype", "sharded_dims", "n_shards",
                          "bytes"} for l in info["leaves"])
    assert info["plan"] == {"kind": "dims", "dims": [1, 2, 1]}
    assert info["topology"]["axes"][0]["name"] == "ici"
    assert info["total_bytes"] == 4 * 2 * 4 + 3

    # corruption is diagnosable: a missing shard file fails loudly
    base = os.path.join(d, "step_00000002")
    shard = next(n for n in os.listdir(os.path.join(base, "shard_00000")))
    os.remove(os.path.join(base, "shard_00000", shard))
    proc = subprocess.run([sys.executable, tool, d, "--json"],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "missing" in proc.stderr
