"""Quickstart: DSP in 40 lines.

Builds the paper's 2D (spatial-temporal) transformer, runs it under Dynamic
Sequence Parallelism on a simulated 8-device mesh, and shows the headline
property: the compiled program contains exactly TWO all-to-alls per layer
pair (Table 2) and matches the single-device reference bit-for-bit-ish.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp

from repro.analysis.roofline import parse_collectives
from repro.models.transformer2d import (T2DConfig, init_t2d, forward,
                                        make_spmd_forward)

# a small video DiT: 4 blocks (2 spatial + 2 temporal), d=128
cfg = T2DConfig(name="quickstart", n_layers=4, d_model=128, n_heads=8,
                d_ff=256, in_dim=16, dtype=jnp.float32)
params = init_t2d(jax.random.PRNGKey(0), cfg)

# latent video: batch 2, 16 frames, 32 spatial tokens
x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32, cfg.in_dim))
t = jax.random.uniform(jax.random.PRNGKey(2), (2,))

# single-device reference
ref = forward(params, x, t, cfg, backend="ref", remat=False)

# DSP on a (data=2, model=4) mesh: sequence sharded on T, dynamically
# switched to S for the temporal stage — one all-to-all per switch
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
dsp_fwd = jax.jit(make_spmd_forward(cfg, mesh, mode="dsp", backend="ref"))
out = dsp_fwd(params, x, t)

err = float(jnp.abs(out - ref).max()) / float(jnp.abs(ref).max())
print(f"DSP vs single-device relative error: {err:.2e}")

stats = parse_collectives(dsp_fwd.lower(params, x, t).compile().as_text())
pairs = cfg.n_layers // 2
print(f"collectives: {stats.by_kind_count}  "
      f"(expect all-to-all == 2 x {pairs} layer pairs)")
assert stats.by_kind_count.get("all-to-all") == 2 * pairs
assert err < 1e-4
print("OK — dynamic switch == 2 all-to-alls per layer pair, exact output")
