"""Everything a run draws from ``--seed``: the model's weights and the
video batches of every step.

The program receives only what these functions make.  Weights are drawn
leaf by leaf, each leaf from its own key, so that one leaf can be drawn
again alone (the update check compares the master weights after three
steps with the weights they started from, without keeping a copy).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# std of the drawn adaLN modulation, biases and norm-scale offsets: the
# program's adaLN-zero init would make every block the identity, where
# attention could never reach the loss
DRAWN_STD = 0.02
_DATA_STREAM = 0xDA7A


def base_key(seed: int) -> jax.Array:
    """A key for any whole number: the seed is taken modulo 2**64 and
    folded in 31 bits at a time, so seeds past 32 bits stay distinct."""
    s = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(s & 0x7FFFFFFF)
    for part in ((s >> 31) & 0x7FFFFFFF, s >> 62):
        key = jax.random.fold_in(key, part)
    return key


def leaf_paths(shapes) -> list:
    """Leaf paths of a params pytree, in ``tree_flatten`` order, as
    '/'-joined strings."""
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in flat]


def _draw(key, path: str, shape, dtype):
    name = path.rsplit("/", 1)[-1]
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "w":
        std = DRAWN_STD if "/mod/" in path else shape[-2] ** -0.5
        v = z * std
    elif name == "scale":
        v = 1.0 + DRAWN_STD * z
    else:                                      # biases
        v = DRAWN_STD * z
    return v.astype(dtype)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _draw_leaf(key, index: int, path: str, shape, dtype):
    return _draw(jax.random.fold_in(key, index), path, shape, dtype)


def init_params(seed: int, shapes):
    """Weights shaped as ``shapes`` (a pytree of ShapeDtypeStructs, e.g.
    ``jax.eval_shape`` of the program's init), drawn from ``seed`` in one
    jitted call on the default device, in the dtypes given."""
    flat, treedef = jax.tree_util.tree_flatten(shapes)
    paths = leaf_paths(shapes)
    spec = tuple((p, tuple(s.shape), jnp.dtype(s.dtype).name)
                 for p, s in zip(paths, flat))
    return jax.tree_util.tree_unflatten(
        treedef, _init_flat(base_key(seed), spec))


@functools.partial(jax.jit, static_argnums=(1,))
def _init_flat(key, spec):
    return [_draw(jax.random.fold_in(key, i), p, s, jnp.dtype(d))
            for i, (p, s, d) in enumerate(spec)]


def init_leaf(seed: int, shapes, index: int):
    """Leaf ``index`` of ``init_params(seed, shapes)`` alone, equal to it
    bit for bit."""
    flat = jax.tree_util.tree_leaves(shapes)
    s = flat[index]
    return _draw_leaf(base_key(seed), index, leaf_paths(shapes)[index],
                      tuple(s.shape), jnp.dtype(s.dtype).name)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _video(key, step, batch, temporal, spatial, in_dim):
    k = jax.random.fold_in(jax.random.fold_in(key, _DATA_STREAM), step)
    k1, k2, k3 = jax.random.split(k, 3)
    shape = (batch, temporal, spatial, in_dim)
    return {"x": jax.random.normal(k1, shape),
            "t": jax.random.uniform(k2, (batch,)),
            "target": jax.random.normal(k3, shape)}


def video_batch(seed: int, step: int, *, batch: int, temporal: int,
                spatial: int, in_dim: int):
    """The batch of training step ``step`` (0-based): patched video
    latents ``x`` and diffusion targets, both (B, T, S, in_dim) standard
    normal, and timesteps ``t`` uniform in [0, 1).  Every step's rows
    differ."""
    return _video(base_key(seed), jnp.uint32(step), batch, temporal,
                  spatial, in_dim)
