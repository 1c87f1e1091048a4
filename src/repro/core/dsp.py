"""Dynamic Sequence Parallelism primitives (paper Table 2).

Two equivalent implementations of the same abstraction are provided:

* **explicit** (paper-faithful) — functions that run *inside* ``shard_map``
  and issue the collective directly: ``dynamic_switch`` is one tiled
  all-to-all (volume M/N per device), ``gather`` is one all-gather (volume M),
  ``split`` is a local slice (zero communication).  These mirror the paper's
  four-function PyTorch API one-to-one.

* **auto** (compiler path) — the same transitions expressed as sharding
  constraints on globally-shaped arrays under ``jit``; XLA SPMD emits the
  identical collectives (asserted by tests that parse the compiled HLO).

Both operate on the ``model`` mesh axis by default (the SP axis of the
production mesh).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.layout import SeqLayout, ParallelContext

# ---------------------------------------------------------------------------
# Explicit (shard_map-level) primitives — the paper's API.
# ---------------------------------------------------------------------------


def dynamic_switch(x: jax.Array, cur_shard: int, tgt_shard: int,
                   axis_name: str = "model") -> jax.Array:
    """Switch the sharded sequence dimension from ``cur_shard`` to ``tgt_shard``.

    Exactly one tiled all-to-all; per-device volume M/N (paper Table 2 row
    ``s_i -> s_j``).  The local view of dim ``cur_shard`` grows by N and dim
    ``tgt_shard`` shrinks by N.
    """
    if cur_shard == tgt_shard:
        return x
    n = jax.lax.axis_size(axis_name)
    if x.shape[tgt_shard] % n:
        raise ValueError(
            f"dynamic_switch: dim {tgt_shard} (size {x.shape[tgt_shard]}) "
            f"not divisible by SP size {n}")
    return jax.lax.all_to_all(x, axis_name, split_axis=tgt_shard,
                              concat_axis=cur_shard, tiled=True)


def split(x: jax.Array, tgt_shard: int, axis_name: str = "model") -> jax.Array:
    """s_hat -> s_i : slice the local shard out of a replicated sequence.

    Zero communication (paper Table 2 row ``s_hat -> s_i``).
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    if x.shape[tgt_shard] % n:
        raise ValueError(
            f"split: dim {tgt_shard} (size {x.shape[tgt_shard]}) not divisible by {n}")
    size = x.shape[tgt_shard] // n
    return jax.lax.dynamic_slice_in_dim(x, idx * size, size, axis=tgt_shard)


def gather(x: jax.Array, cur_shard: int, axis_name: str = "model") -> jax.Array:
    """s_i -> s_hat : all-gather the full sequence (volume M, used only at
    model boundaries / rare global ops)."""
    return jax.lax.all_gather(x, axis_name, axis=cur_shard, tiled=True)


def dsp_shard_batch(batch, tgt_shard: int, axis_name: str = "model"):
    """The paper's ``dsp_dataloader``: every member of an SP group holds the
    same global batch; slice each array along ``tgt_shard`` locally."""
    return jax.tree_util.tree_map(lambda a: split(a, tgt_shard, axis_name), batch)


# ---------------------------------------------------------------------------
# Auto (jit / sharding-constraint) primitives.
# ---------------------------------------------------------------------------


def switch_constraint(x: jax.Array, ctx: ParallelContext, layout: SeqLayout,
                      tgt_shard: int) -> tuple[jax.Array, SeqLayout]:
    """Compiler-path dynamic switch: re-constrain the sharded dim.

    Under jit+SPMD the layout change lowers to one all-to-all — verified by
    tests/test_hlo_collectives.py.
    """
    new_layout = layout.switched(tgt_shard)
    return ctx.constrain(x, new_layout), new_layout


def gather_constraint(x: jax.Array, ctx: ParallelContext,
                      layout: SeqLayout) -> tuple[jax.Array, SeqLayout]:
    new_layout = layout.gathered()
    return ctx.constrain(x, new_layout), new_layout


def split_constraint(x: jax.Array, ctx: ParallelContext, layout: SeqLayout,
                     tgt_shard: int) -> tuple[jax.Array, SeqLayout]:
    new_layout = layout.split(tgt_shard)
    return ctx.constrain(x, new_layout), new_layout


# ---------------------------------------------------------------------------
# Communication-volume model (paper Table 2) — used by benchmarks and the
# planner; analytic, per-device bytes.
# ---------------------------------------------------------------------------


def comm_volume_bytes(primitive: str, global_bytes: int, n: int) -> float:
    """Per-device communication volume of one DSP primitive on a tensor of
    ``global_bytes`` (= M) with SP size ``n`` (= N).

    Convention — paper Table 2 counts the per-device SHARD that a collective
    re-tiles or materialises, not the on-wire fraction:

      switch  s_i -> s_j   : M/N   one tiled all-to-all re-tiles each
                                   device's full M/N shard (on the wire each
                                   device sends (N-1)/N of that shard; the
                                   paper and this repo fold the constant into
                                   M/N, and HLO measurement uses the same
                                   result-bytes convention, see
                                   analysis.roofline.parse_collectives)
      gather  s_i -> s_hat : M     all-gather materialises the full sequence
                                   on every device
      split   s_hat -> s_i : 0     local slice
      keep    s_i -> s_i   : 0

    This single constant is shared by the switching planner
    (``core.plan``), the schedule executor (``core.schedule``), and
    ``benchmarks/comm_volume.py`` — planned and analytic volumes are
    comparable by construction.
    """
    if primitive == "keep":
        return 0.0
    if primitive == "switch":
        return global_bytes / n
    if primitive == "split":
        return 0.0
    if primitive == "gather":
        return float(global_bytes)
    raise ValueError(f"unknown primitive {primitive!r}")


def per_device_bytes(strategy: str, global_bytes: float, n: int, *,
                     kv_bytes: Optional[float] = None,
                     kv_heads: Optional[int] = None,
                     outer: int = 1) -> float:
    """Per-device communication volume of one STAGE executed with an SP
    strategy (Table 3 generalised) — the single constant
    ``benchmarks/comm_volume.py`` AND the strategy DP
    (``core.plan.plan_strategy_dp`` via ``Topology.embedded_seconds``)
    price from, so planned-vs-measured byte ratios are 1.00 by
    construction.

    ``global_bytes`` is the residual stream (M); ``kv_bytes`` the K/V
    activations (default 2M, the MHA convention).  Units per strategy:

      dsp       2M/N   the layer pair's TWO boundary switches (M/N each,
                       ``comm_volume_bytes("switch", ...)``)
      ulysses   2M/N + kv/N   q + out a2as plus the K/V head-scatter a2as;
                       when ``kv_heads`` does not divide by N (GQA) the K/V
                       scatter degrades to replication: 2M/N + kv
      ring      kv     N ppermute hops of kv/N (``core.ring``)
      megatron  4M     ONE AG/RS-wrapped block (2 collectives x 2M each,
                       ``core.megatron_sp``); a 2D-transformer layer pair
                       wraps both blocks = 8M
      hybrid    (2M + kv)/N + kv*outer/N   USP: inner a2as move host-local
                       shards, the outer ring streams kv/N per hop for
                       ``outer`` hops (the outer-axis size)

    Measured counterparts use the HLO result-bytes convention of
    ``analysis.roofline.parse_collectives`` (while bodies x trip count).
    """
    m = float(global_bytes)
    kv = float(kv_bytes) if kv_bytes is not None else 2.0 * m
    if strategy == "dsp":
        return 2.0 * comm_volume_bytes("switch", m, n)
    if strategy == "ulysses":
        if kv_heads is not None and kv_heads % n:
            return 2.0 * m / n + kv          # K/V replicated (all-gather)
        return 2.0 * m / n + kv / n
    if strategy == "ring":
        return kv
    if strategy == "megatron":
        return 4.0 * m
    if strategy == "hybrid":
        return (2.0 * m + kv) / n + kv * outer / n
    raise ValueError(f"unknown strategy {strategy!r}")


__all__ = [
    "dynamic_switch", "split", "gather", "dsp_shard_batch",
    "switch_constraint", "gather_constraint", "split_constraint",
    "comm_volume_bytes", "per_device_bytes",
]
