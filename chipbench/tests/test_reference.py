"""The benchmark's own seeded data and float32 reference against the
program, at the SMOKE configurations on the CPU."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare, data, reference
from chipbench.drivers import train as driver
from chipbench.harness import ROOT

SHAPE = dict(batch=2, temporal=4, spatial=8)
OPT = reference.Optimizer(peak_lr=1e-3, warmup_steps=1, total_steps=3,
                          min_lr_ratio=0.1, b1=0.9, b2=0.95, eps=1e-8,
                          weight_decay=0.1, grad_clip=1.0)


def _program(arch):
    from repro import configs
    from repro.models.transformer2d import init_t2d
    cfg = configs.get(arch).smoke
    shapes = jax.eval_shape(lambda k: init_t2d(k, cfg), jax.random.PRNGKey(0))
    return cfg, shapes


def test_seed_draws_distinct_and_repeatable_weights_and_batches():
    _, shapes = _program("transformer2d-720m")
    big = 2 ** 31 + 12345
    a = data.init_params(big, shapes)
    b = data.init_params(big, shapes)
    c = data.init_params(big + 2 ** 32, shapes)
    for x, y, z in zip(*(jax.tree_util.tree_leaves(t) for t in (a, b, c))):
        np.testing.assert_array_equal(x, y)
        assert not np.array_equal(x, z)
    mod = a["layers"]["spatial"]["mod"]["proj"]["w"]
    assert float(jnp.abs(mod).max()) > 0      # adaLN drawn, not zero
    for i, leaf in enumerate(jax.tree_util.tree_leaves(a)):
        np.testing.assert_array_equal(data.init_leaf(big, shapes, i), leaf)
    s0 = data.video_batch(big, 0, in_dim=16, **SHAPE)
    s1 = data.video_batch(big, 1, in_dim=16, **SHAPE)
    assert not np.array_equal(s0["x"], s1["x"])
    np.testing.assert_array_equal(
        s0["x"], data.video_batch(big, 0, in_dim=16, **SHAPE)["x"])


@pytest.mark.parametrize("arch", ["transformer2d-720m", "transformer2d-3b"])
def test_reference_loss_and_grads_match_the_program(arch):
    from repro.models.transformer2d import t2d_loss
    cfg, shapes = _program(arch)
    seed = 7
    params = data.init_params(seed, shapes)
    batch = data.video_batch(seed, 0, in_dim=cfg.in_dim, **SHAPE)
    loss, grads = jax.value_and_grad(
        lambda p: t2d_loss(p, batch, cfg, backend="ref")[0])(params)
    ref = reference.Reference(driver.program_model(cfg), OPT, seed, shapes,
                              devices=jax.devices()[:1])
    rloss, g_top, g_blocks = ref.loss_and_grads(batch)
    np.testing.assert_allclose(rloss, float(loss), rtol=1e-5)
    want = {k: float(jnp.sum(jnp.square(v))) for k, v in zip(
        data.leaf_paths(grads), jax.tree_util.tree_leaves(grads))}
    got = ref.leaf_sq(g_top, g_blocks)
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-4, err_msg=k)


def test_reference_adamw_matches_the_program_optimizer():
    from repro.optim.adamw import OptConfig, apply_adamw, init_opt_state
    from repro.models.transformer2d import t2d_loss
    cfg, shapes = _program("transformer2d-720m")
    seed = 11
    params = data.init_params(seed, shapes)
    ocfg = OptConfig(**{k: getattr(OPT, k) for k in driver.OPT_FIELDS})
    state = init_opt_state(params, ocfg)
    ref = reference.Reference(driver.program_model(cfg), OPT, seed, shapes,
                              devices=jax.devices()[:1])
    for i in range(3):
        batch = data.video_batch(seed, i, in_dim=cfg.in_dim, **SHAPE)
        loss, grads = jax.value_and_grad(
            lambda p: t2d_loss(p, batch, cfg, backend="ref")[0])(params)
        params, state, _ = apply_adamw(params, grads, state, ocfg)
        rloss, _, _ = ref.train_step(batch)
        np.testing.assert_allclose(rloss, float(loss), rtol=1e-5)
    delta = {k: float(jnp.sum(jnp.square(m - data.init_leaf(seed, shapes, i))))
             for i, (k, m) in enumerate(zip(
                 data.leaf_paths(shapes),
                 jax.tree_util.tree_leaves(state["master"])))}
    got = {k: v[0] for k, v in compare.sums(ref.delta_pieces()).items()}
    for k in delta:
        np.testing.assert_allclose(got[k], delta[k], rtol=1e-3, err_msg=k)


def test_round_to_bfloat16_is_the_nearest_even():
    x = jax.random.normal(jax.random.PRNGKey(3), (4096,)) * jnp.logspace(
        -40, 30, 4096, base=2.0)
    # ties: halfway between two bfloat16 values, both parities
    ties = jnp.array([1.0 + 2.0 ** -8, 1.0 + 3 * 2.0 ** -8, -(1.0 + 2.0 ** -8),
                      2.0 ** -130 * (1 + 2.0 ** -8)], jnp.float32)
    for a in (x, ties):
        want = a.astype(jnp.bfloat16).astype(jnp.float32)
        got = jax.jit(reference.round_to, static_argnums=1)(a, "bfloat16")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(reference.round_to(x, "float32"), x)
    with pytest.raises(ValueError):
        reference.round_to(x, "float16")


def test_cosine_gaps_of_a_run_against_itself_are_nought():
    cfg, shapes = _program("transformer2d-720m")
    traffic = dict(batch=2, temporal=4, spatial=8,
                   optimizer={k: getattr(OPT, k) for k in driver.OPT_FIELDS})
    args = (driver.program_model(cfg), traffic, 5, shapes,
            jax.devices()[:1], 2)
    a = driver.run_reference(*args, keep=True)
    b = driver.run_reference(*args, against=a)
    got = compare.numbers(b, a, b["cos"])
    assert max(got.values()) < 1e-6, got
    assert set(b["cos"]["grad"]) == set(data.leaf_paths(shapes))


SPLIT = r"""
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
from chipbench.drivers import train
from chipbench.tests.test_reference import OPT, _program
cfg, shapes = _program("transformer2d-3b")
traffic = dict(batch=1, temporal=8, spatial=8,
               optimizer={{k: getattr(OPT, k) for k in train.OPT_FIELDS}})
out = {{}}
for n in (1, 4):
    r = train.run_reference(train.program_model(cfg), traffic, 9, shapes,
                            jax.devices()[:n], 2)
    out[n] = [r["losses"], r["grad_sq"], r["delta_sq"]]
print(json.dumps(out))
"""


def test_reference_split_over_four_devices_reads_as_on_one():
    code = SPLIT.format(root=ROOT, src=os.path.join(ROOT, "src"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    one, four = json.loads(p.stdout.strip().splitlines()[-1]).values()
    np.testing.assert_allclose(four[0], one[0], rtol=1e-5)
    for k in one[1]:
        np.testing.assert_allclose(four[1][k], one[1][k], rtol=1e-4,
                                   err_msg=k)
        np.testing.assert_allclose(four[2][k], one[2][k], rtol=1e-3,
                                   err_msg=k)
