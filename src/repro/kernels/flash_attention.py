"""Pallas TPU flash attention (forward) with online softmax.

TPU-native design notes (vs. the CUDA flash-attention the paper's baselines
use): the kernel tiles Q/K/V into VMEM with ``BlockSpec``s, keeps the running
(max, sum, accumulator) in VMEM scratch across the *sequential* innermost
grid dimension (TPU grids execute the last axis in order, so scratch carries
state between K blocks), and sizes blocks to the MXU (128x128 systolic
array).  GQA is handled structurally: the K/V ``index_map`` folds the query
head onto its KV group (``h // group``), so grouped heads re-read the same
KV block from HBM without materialising repeats.

Supports: causal masking, sliding-window (attend to (pos-window, pos]),
logit soft-capping (Gemma-2), GQA/MQA, padded KV lengths, and a global
``q_offset`` so the same kernel serves decode (Sq small, offset = cache
position) and prefill.

Backward runs through the ``attention_ref`` oracle via a custom VJP defined
in ops.py (recompute-based), which is the standard TPU approach when the
forward is the hot spot being optimised.

``ops.flash_attention`` runs this kernel only when the keys span more than
one KV block.  With a single block the online softmax has no second block
to rescale against, and the grid (one program per sequence and head) and
the padding of the keys to the block are the whole cost, so ops.py runs
that forward as XLA's fused attention instead.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)
LANES = 128   # TPU lane width; m/l scratch is lane-replicated


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                      sm_scale: float, causal: bool, window: Optional[int],
                      softcap: Optional[float], kv_len: int, q_offset: int,
                      block_q: int, block_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q0 = qi * block_q + q_offset           # global position of this Q block
    k0 = ki * block_k

    run = k0 < kv_len                       # skip fully-padded KV blocks
    if causal:
        run = jnp.logical_and(run, k0 <= q0 + block_q - 1)
    if window is not None:
        run = jnp.logical_and(run, k0 + block_k - 1 > q0 - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        rows = q0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = k0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = cols < kv_len
        if causal:
            mask = jnp.logical_and(mask, cols <= rows)
        if window is not None:
            mask = jnp.logical_and(mask, cols > rows - window)
        s = jnp.where(mask, s, MASK_VALUE)

        m_prev = m_ref[...]                          # (bq, LANES), lane-replicated
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)   # (bq, 1)
        m_next = jnp.maximum(m_prev, m_cur)          # (bq, LANES)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next[:, :1])               # (bq, bk)
        p = jnp.where(mask, p, 0.0)                  # dead rows stay at 0
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + pv
        m_ref[...] = m_next

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)              # fully-masked (padded) rows
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = False, window: Optional[int] = None,
                        softcap: Optional[float] = None,
                        scale: Optional[float] = None, q_offset: int = 0,
                        kv_len: Optional[int] = None,
                        block_q: int = 128, block_k: int = 128,
                        interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D).  Sq % block_q == 0 and
    Skv % block_k == 0 (ops.py pads).  ``kv_len`` masks KV padding."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0, (sq, skv, block_q, block_k)
    kv_len = skv if kv_len is None else kv_len
    scale = d ** -0.5 if scale is None else scale
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    grid = (b, hq, sq // block_q, skv // block_k)
    kernel = functools.partial(
        _flash_fwd_kernel, sm_scale=scale, causal=causal, window=window,
        softcap=softcap, kv_len=kv_len, q_offset=q_offset,
        block_q=block_q, block_k=block_k)

    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, qi, ki: (b_, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, qi, ki: (b_, h // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, qi, ki: (b_, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h, qi, ki: (b_, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="flash_fwd",
    )
    return call(q, k, v)
