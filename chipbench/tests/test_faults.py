"""A whole run at the SMOKE size on the CPU, past the harness's look for a
chip, with the timed path broken underneath: ``correct`` has to come out
false for each fault a one-chip training cell can have, and for a
gradient or an update that points elsewhere with its norm kept, and true
without one.  The control (the reference with float8 products) has to
fail the cell's limits too."""
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench.harness import Benchmark

BENCH = Benchmark()
ONE = "t2d-720m.train.16x256"
SMALL = dict(batch=2, temporal=4, spatial=8)


def _run(cell, devices):
    from chipbench.run import run_cell
    traffic = dict(BENCH.traffic(BENCH.cell(cell)["traffic"]), **SMALL)
    return run_cell(BENCH, cell, 2 ** 31 + 99, 0.5, 0, devices,
                    t_start=time.monotonic(), smoke=True, traffic=traffic)


def test_unbroken_run_is_correct():
    r = _run(ONE, jax.devices()[:1])
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-1] == "checks"


def test_state_left_unchanged_is_not_correct(monkeypatch):
    from repro.train import trainer

    def unchanged(params, grads, state, cfg):
        return params, state, {"lr": jnp.zeros(()), "grad_norm": jnp.zeros(())}

    monkeypatch.setattr(trainer, "apply_adamw", unchanged)
    r = _run(ONE, jax.devices()[:1])
    assert not r["correct"]
    assert r["checks"]["grad_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out_is_not_correct(monkeypatch):
    from repro.models import transformer2d
    whole = transformer2d.t2d_loss

    def half(params, batch, cfg, **kw):
        t = batch["x"].shape[1] // 2
        return whole(params, {k: (v[:, :t] if v.ndim > 1 else v)
                              for k, v in batch.items()}, cfg, **kw)

    monkeypatch.setattr(transformer2d, "t2d_loss", half)
    r = _run(ONE, jax.devices()[:1])
    assert not r["correct"], r["checks"]


def _turned(tree):
    """Each leaf's values moved one place along its last axis: the same
    norm, another direction."""
    return jax.tree_util.tree_map(lambda a: jnp.roll(a, 1, axis=-1), tree)


@pytest.mark.parametrize("what", ["gradient", "update"])
def test_a_turned_gradient_or_update_is_not_correct(monkeypatch, what):
    from repro.train import trainer
    adamw = trainer.apply_adamw

    def turned(params, grads, state, cfg):
        if what == "gradient":
            return adamw(params, _turned(grads), state, cfg)
        new, st, om = adamw(params, grads, state, cfg)
        master = jax.tree_util.tree_map(
            lambda m, d: d + jnp.roll(m - d, 1, axis=-1),
            st["master"], state["master"])
        st = dict(st, master=master)
        return (jax.tree_util.tree_map(lambda m, p: m.astype(p.dtype),
                                       master, new), st, om)

    monkeypatch.setattr(trainer, "apply_adamw", turned)
    r = _run(ONE, jax.devices()[:1])
    assert not r["correct"]
    name = "grad_cos_gap" if what == "gradient" else "update_cos_gap"
    assert r["checks"][name]["value"] > r["checks"][name]["limit"]


def test_control_fails_the_limits():
    from chipbench import compare
    from chipbench.drivers import train
    from repro import configs
    from repro.models.transformer2d import init_t2d
    cfg = configs.get("transformer2d-720m").smoke
    shapes = jax.eval_shape(lambda k: init_t2d(k, cfg),
                            jax.random.PRNGKey(0))
    traffic = dict(BENCH.traffic(BENCH.cell(ONE)["traffic"]), **SMALL)
    model = train.program_model(cfg)
    args = (model, traffic, 5, shapes, jax.devices()[:1], 3)
    ref = train.run_reference(*args, keep=True)
    control = train.run_reference(*args, precision="fp8", against=ref)
    checks = compare.checks(compare.numbers(control, ref, control["cos"]),
                            BENCH.limits(ONE))
    assert not all(c["ok"] for c in checks.values()), checks
