"""Ring-Attention baseline (Li et al. 2021; Liu et al. 2023).

K/V blocks rotate around the device ring via ``ppermute`` while each device
keeps its Q shard; partial attention is merged with a numerically-stable
online softmax (the blockwise trick of Liu et al.).  Total per-device volume
is the full K+V activation (2M for k,v of size M each over N-1 hops of M/N),
matching the paper's Table 3 entry.  Runs inside ``shard_map``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.overlap import ring_stream

NEG_INF = -1e30


def stream_bytes(global_bytes: float, n: int, *, kv_bytes=None) -> float:
    """Per-device volume of one ring attention, routed through the shared
    constant ``core.dsp.per_device_bytes("ring", ...)`` (= the full K/V
    activation, kv, default 2M — N hops of kv/N each; Table 3)."""
    from repro.core.dsp import per_device_bytes
    return per_device_bytes("ring", global_bytes, n, kv_bytes=kv_bytes)


def _block_attn(q, k, v, q_pos, k_pos, scale: float, causal: bool):
    """One (Q-shard x K-block) partial attention.  Shapes:
    q: (B, Sq, H, D), k/v: (B, Sk, H, D); returns (o, m, l) un-normalised."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]          # (Sq, Sk)
        s = jnp.where(mask[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)                              # (B, H, Sq)
    # guard fully-masked rows
    m_safe = jnp.where(m <= NEG_INF / 2, 0.0, m)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1)                              # (B, H, Sq)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                   preferred_element_type=jnp.float32)
    return o, m_safe, l, (m <= NEG_INF / 2)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str = "model", causal: bool = False,
                   scale: Optional[float] = None) -> jax.Array:
    """q: local (B, S/N, H, D) sharded along the sequence; k, v may carry
    fewer heads (B, S/N, Hkv, D) with H % Hkv == 0 — GQA rotates the small
    K/V blocks and repeats them up to H locally after each hop.  Returns the
    local output shard (B, S/N, H, D)."""
    idx = jax.lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    q_pos = idx * s_local + jnp.arange(s_local)

    def fold(t, src, blocks, carry):
        k_blk, v_blk = blocks                 # owned by device ``src``
        o, m, l, any_valid = carry
        # GQA: the ring streams the SMALL K/V heads (that is the whole
        # bandwidth win — per-hop volume is kv/N, not the Q width); repeat
        # up to the Q head count only after the transfer, locally
        rep = h // k_blk.shape[2]
        if rep > 1:
            k_blk = jnp.repeat(k_blk, rep, axis=2)
            v_blk = jnp.repeat(v_blk, rep, axis=2)
        k_pos = src * s_local + jnp.arange(s_local)
        o_b, m_b, l_b, dead = _block_attn(q, k_blk, v_blk, q_pos, k_pos, scale, causal)
        # online-softmax merge; dead rows (fully masked block) contribute nothing
        m_new = jnp.where(dead, m, jnp.maximum(m, m_b))
        c_old = jnp.exp(m - m_new)
        c_new = jnp.where(dead, 0.0, jnp.exp(m_b - m_new))
        o = o * c_old[..., None].transpose(0, 2, 1, 3) + o_b * c_new[..., None].transpose(0, 2, 1, 3)
        l = l * c_old + l_b * c_new
        any_valid = any_valid | ~dead
        return o, m_new, l, any_valid

    o0 = jnp.zeros((b, s_local, h, d), jnp.float32)
    m0 = jnp.full((b, h, s_local), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_local), jnp.float32)
    valid0 = jnp.zeros((b, h, s_local), bool)
    # mark constant-initialised carries as varying over the ring axis so the
    # scan carry types line up under shard_map's vma tracking
    carry0 = jax.lax.pcast((o0, m0, l0, valid0), (axis_name,),
                           to="varying")
    # the shared chunk/rotate helper (one ppermute hop per K/V block)
    o, m, l, any_valid = ring_stream((k, v), carry0, fold,
                                     axis_name=axis_name)
    l = jnp.where(any_valid, l, 1.0)
    out = o / l[..., None].transpose(0, 2, 1, 3)
    return out.astype(q.dtype)
