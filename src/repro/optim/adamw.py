"""AdamW in pure JAX (no optax dependency): f32 optimizer state over
arbitrary-dtype params, global-norm clipping, warmup+cosine schedule.

Optimizer state is a pytree shaped like the params, so ZeRO sharding is
"for free": the launcher applies the same PartitionSpecs to m/v/master as to
the parameters (sharded over the ``data`` axis -> ZeRO-3).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro import tracing


@dataclasses.dataclass(frozen=True)
class OptConfig:
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_ratio: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: Optional[float] = 1.0
    use_master: bool = True        # keep f32 master copy for bf16 params
    state_dtype: Any = jnp.float32  # m/v dtype; bf16 for 400B-class runs
                                    # (8-bit-Adam-style memory cut, see
                                    # DESIGN.md fault-tolerance/memory notes)


def schedule(cfg: OptConfig, step: jax.Array) -> jax.Array:
    """Linear warmup then cosine decay to min_lr_ratio * peak."""
    step = step.astype(jnp.float32)
    warm = cfg.peak_lr * step / max(cfg.warmup_steps, 1)
    frac = jnp.clip((step - cfg.warmup_steps) /
                    max(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
    cos = cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * 0.5 * (
        1 + jnp.cos(math.pi * frac))
    return jnp.where(step < cfg.warmup_steps, warm, cfg.peak_lr * cos)


def init_opt_state(params, cfg: OptConfig):
    """Fresh state on the params' devices and shardings.  Every leaf is its
    own buffer (the master is a copy even of f32 params), so a train step
    may donate params and state together."""
    zeros = jax.tree_util.tree_map(
        lambda p: jnp.zeros_like(p, dtype=cfg.state_dtype), params)
    state = {"m": zeros,
             "v": jax.tree_util.tree_map(jnp.copy, zeros),
             "step": jnp.zeros((), jnp.int32)}
    if cfg.use_master:
        state["master"] = jax.tree_util.tree_map(
            lambda p: jnp.array(p, dtype=jnp.float32, copy=True), params)
    return state


def global_norm(tree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def clip_by_global_norm(grads, max_norm: float):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return jax.tree_util.tree_map(lambda g: g * scale, grads), norm


def apply_adamw(params, grads, state, cfg: OptConfig):
    """One AdamW step.  Returns (new_params, new_state, metrics)."""
    with tracing.scope(tracing.ADAMW):
        return _adamw(params, grads, state, cfg)


def _adamw(params, grads, state, cfg: OptConfig):
    step = state["step"] + 1
    lr = schedule(cfg, step)
    gnorm = jnp.zeros(())
    if cfg.grad_clip is not None:
        grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)

    b1c = 1.0 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1.0 - cfg.b2 ** step.astype(jnp.float32)
    ref = state.get("master", params)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32)
        m = (cfg.b1 * m.astype(jnp.float32) + (1 - cfg.b1) * g).astype(cfg.state_dtype)
        v = (cfg.b2 * v.astype(jnp.float32) +
             (1 - cfg.b2) * jnp.square(g)).astype(cfg.state_dtype)
        mh = m.astype(jnp.float32) / b1c
        vh = v.astype(jnp.float32) / b2c
        pf = p.astype(jnp.float32)
        pf = pf - lr * (mh / (jnp.sqrt(vh) + cfg.eps) +
                        cfg.weight_decay * pf)
        return pf, m, v

    flat_ref, treedef = jax.tree_util.tree_flatten(ref)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state["m"])
    flat_v = treedef.flatten_up_to(state["v"])
    out = [upd(p, g, m, v) for p, g, m, v in
           zip(flat_ref, flat_g, flat_m, flat_v)]
    new_master = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])

    flat_p = treedef.flatten_up_to(params)
    new_params = treedef.unflatten([
        nm.astype(p.dtype) for nm, p in
        zip([o[0] for o in out], flat_p)])

    new_state = {"m": new_m, "v": new_v, "step": step}
    if cfg.use_master:
        new_state["master"] = new_master
    return new_params, new_state, {"lr": lr, "grad_norm": gnorm}
