"""Pallas TPU flash attention (forward) with online softmax.

TPU-native design notes (vs. the CUDA flash-attention the paper's baselines
use): the kernel tiles Q/K/V into VMEM with ``BlockSpec``s, keeps the running
(max, sum, accumulator) in VMEM scratch across the *sequential* innermost
grid dimension (TPU grids execute the last axis in order, so scratch carries
state between K blocks).  GQA is handled structurally: the K/V ``index_map``
folds the query head onto its KV group (``h // group``), so grouped heads
re-read the same KV block from HBM without materialising repeats.

Tiles: ``ops.flash_tiles`` picks them from the shape, up to 1024 rows, so
that a grid step holds MXU-sized work rather than paying the per-step
pipeline overhead for a single 128x128 product.  When one KV block covers
every key the kernel keeps no running max or sum: the block's softmax is
the whole softmax, so there is no rescale and no scratch.

Precision: both dots take f32 operands at ``HIGHEST`` and accumulate in
f32 (``_dot``); softmax, max, sum and the accumulator are f32.  The MXU
multiplies bf16, and at the default precision it rounds an f32 operand to
one bf16, which would round p before p·v.

Masks are built only where one exists: causal, a window, or keys padded
past ``kv_len``, each decided statically.  Unmasked calls (the DiT's
spatial attention) build no iota, mask or select.

Supports: causal masking, sliding-window (attend to (pos-window, pos]),
logit soft-capping (Gemma-2), GQA/MQA, padded KV lengths, and a global
``q_offset`` so the same kernel serves decode (Sq small, offset = cache
position) and prefill.

Backward runs through the ``attention_ref`` oracle via a custom VJP defined
in ops.py (recompute-based), which is the standard TPU approach when the
forward is the hot spot being optimised.

``ops.flash_attention`` runs this kernel only when the keys span more than
one 128-key block.  With fewer keys the grid (one program per sequence and
head) and the padding of the keys to the block are the whole cost, so
ops.py runs that forward as XLA's fused attention instead.
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
_NT = (((1,), (1,)), ((), ()))    # a · bᵀ
_NN = (((1,), (0,)), ((), ()))    # a · b


def _dot(a: jax.Array, b: jax.Array, dims) -> jax.Array:
    """f32 product of f32 operands at ``HIGHEST``: at the default precision
    the TPU rounds an f32 operand to one bf16 (measured on a v5e: 1.6e-3
    relative in p·v)."""
    return jax.lax.dot_general(a.astype(jnp.float32), b.astype(jnp.float32),
                               dims, preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *scratch,
                      sm_scale: float, causal: bool, window: Optional[int],
                      softcap: Optional[float], kv_len: int, padded: bool,
                      q_offset: int, block_q: int, block_k: int):
    q0 = pl.program_id(2) * block_q + q_offset   # global position of Q block
    ki = pl.program_id(3)
    k0 = ki * block_k
    masked = causal or window is not None or padded

    def scores():
        s = _dot(q_ref[0, 0], k_ref[0, 0], _NT)
        s = s * sm_scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        if not masked:
            return s, None
        rows = q0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = k0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = cols < kv_len
        if causal:
            mask = jnp.logical_and(mask, cols <= rows)
        if window is not None:
            mask = jnp.logical_and(mask, cols > rows - window)
        return jnp.where(mask, s, MASK_VALUE), mask

    def probs(s, mask, m):
        p = jnp.exp(s - m)
        return p if mask is None else jnp.where(mask, p, 0.0)  # dead rows: 0

    def normalised(acc, l):
        l = jnp.where(l == 0.0, 1.0, l)              # fully-masked (padded) rows
        return (acc / l).astype(o_ref.dtype)

    if not scratch:                                  # one KV block: no carry
        s, mask = scores()
        p = probs(s, mask, jnp.max(s, axis=-1, keepdims=True))
        o_ref[0, 0] = normalised(_dot(p, v_ref[0, 0], _NN),
                                 jnp.sum(p, axis=-1, keepdims=True))
        return

    acc_ref, m_ref, l_ref = scratch

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute():
        s, mask = scores()
        m_prev = m_ref[...]                          # (bq, LANES), lane-replicated
        m_cur = jnp.max(s, axis=-1, keepdims=True)   # (bq, 1)
        m_next = jnp.maximum(m_prev, m_cur)          # (bq, LANES)
        alpha = jnp.exp(m_prev - m_next)
        p = probs(s, mask, m_next[:, :1])            # (bq, bk)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * alpha[:, :1]
                        + _dot(p, v_ref[0, 0], _NN))
        m_ref[...] = m_next

    run = []                                         # blocks a mask empties
    if padded:
        run.append(k0 < kv_len)
    if causal:
        run.append(k0 <= q0 + block_q - 1)
    if window is not None:
        run.append(k0 + block_k - 1 > q0 - window)
    if run:
        pl.when(functools.reduce(jnp.logical_and, run))(_compute)
    else:
        _compute()

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        o_ref[0, 0] = normalised(acc_ref[...], l_ref[:, :1])


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = False, window: Optional[int] = None,
                        softcap: Optional[float] = None,
                        scale: Optional[float] = None, q_offset: int = 0,
                        kv_len: Optional[int] = None,
                        block_q: int = 128, block_k: int = 128,
                        interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D).  Sq % block_q == 0 and
    Skv % block_k == 0 (ops.py pads and picks the tiles).  ``kv_len``
    masks KV padding."""
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

    nk = skv // block_k
    grid = (b, hq, sq // block_q, nk)
    kernel = functools.partial(
        _flash_fwd_kernel, sm_scale=scale, causal=causal, window=window,
        softcap=softcap, kv_len=kv_len, padded=kv_len < skv,
        q_offset=q_offset, block_q=block_q, block_k=block_k)
    scratch = [] if nk == 1 else [
        pltpu.VMEM((block_q, d), jnp.float32),
        pltpu.VMEM((block_q, LANES), jnp.float32),
        pltpu.VMEM((block_q, LANES), jnp.float32),
    ]

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
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="flash_fwd",
    )
    return call(q, k, v)
