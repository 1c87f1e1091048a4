"""Attention: MHA / GQA / MQA with qk-norm, RoPE, sliding window, logit
soft-capping, cross-attention, and KV-cache decode.

Tensors are (B, S, C) at the block boundary; the kernel path uses
(B, H, S, D).  ``backend="pallas"`` routes through the Pallas flash kernel,
``backend="ref"`` through the jnp oracle (used by the dry-run so XLA's cost
model accounts the attention FLOPs).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.ops import flash_attention
from repro.models import layers as L


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qk_norm: bool = False
    rope: bool = True
    rope_theta: float = 10000.0
    window: Optional[int] = None          # sliding window (None = full)
    softcap: Optional[float] = None       # attention logit soft-cap (gemma2)
    bias: bool = False
    scale: Optional[float] = None         # override 1/sqrt(head_dim)


def init_attention(key, cfg: AttnConfig, *, dtype=jnp.float32,
                   cross: bool = False):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": L.init_linear(k1, d, h * dh, bias=cfg.bias, dtype=dtype),
        "wk": L.init_linear(k2, d, hkv * dh, bias=cfg.bias, dtype=dtype),
        "wv": L.init_linear(k3, d, hkv * dh, bias=cfg.bias, dtype=dtype),
        "wo": L.init_linear(k4, h * dh, d, bias=cfg.bias, dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = L.init_norm(dh, dtype=dtype)
        p["k_norm"] = L.init_norm(dh, dtype=dtype)
    return p


def init_kv_cache(batch: int, cfg: AttnConfig, max_len: int, *,
                  dtype=jnp.float32):
    shape = (batch, cfg.n_kv_heads, max_len, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "pos": jnp.zeros((), jnp.int32)}


def attention(p, x, cfg: AttnConfig, *, causal: bool = True,
              positions: Optional[jax.Array] = None,
              x_kv: Optional[jax.Array] = None,
              cache: Optional[dict] = None,
              sharder=None,
              backend: str = "pallas"):
    """x: (B, S, C).  ``x_kv`` switches to cross-attention (no cache/rope on
    q positions mirrors enc-dec usage).  With ``cache`` given, runs
    incremental decoding: writes K/V at cache['pos'] and attends to the
    prefix; returns (out, new_cache), else just out."""
    b, s, _ = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    src = x if x_kv is None else x_kv
    s_kv = src.shape[1]

    q = L.linear(p["wq"], x).reshape(b, s, h, dh)
    k = L.linear(p["wk"], src).reshape(b, s_kv, hkv, dh)
    v = L.linear(p["wv"], src).reshape(b, s_kv, hkv, dh)

    if cfg.qk_norm:
        q = L.rms_norm(p["q_norm"], q)
        k = L.rms_norm(p["k_norm"], k)

    if positions is None:
        base = cache["pos"] if cache is not None else 0
        if jnp.ndim(base) == 1:          # per-slot decode positions: (B, S)
            positions = base[:, None] + jnp.arange(s)
        else:
            positions = base + jnp.arange(s)
    if cfg.rope and x_kv is None:
        q = L.apply_rope(q, positions, theta=cfg.rope_theta)
        k = L.apply_rope(k, positions, theta=cfg.rope_theta)

    q = q.transpose(0, 2, 1, 3)           # (B, H, S, D)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)

    new_cache = None
    q_offset = 0
    if cache is not None:
        if sharder is not None:
            # single-token q/k/v are tiny: replicate across the model axis so
            # the seq-sharded cache is attended LOCALLY (DSP decode)
            q = sharder.decode_heads(q)
            k = sharder.decode_heads(k)
            v = sharder.decode_heads(v)
        pos = cache["pos"]
        if "table" in cache:
            # paged decode (block pool): the cache holds BLOCKS
            # (n_blocks, Hkv, block, D) and ``table`` (B, blocks_per_slot)
            # maps each row's logical positions onto physical blocks.  The
            # write is one batched scatter at (block, offset) — positions
            # land inside blocks the row OWNS, so rows never collide — and
            # the read gathers each row's blocks along the (replicated)
            # block dim, i.e. both stay local on the sequence-sharded
            # leaves exactly like the slot pool's row-wise update.
            table = cache["table"]
            bsz = cache["k"].shape[2]
            p_new = pos[:, None] + jnp.arange(s)           # (B, s)
            phys = jnp.take_along_axis(table, p_new // bsz, axis=1)
            off = p_new % bsz
            ck = cache["k"].at[phys, :, off].set(
                k.transpose(0, 2, 1, 3).astype(cache["k"].dtype))
            cv = cache["v"].at[phys, :, off].set(
                v.transpose(0, 2, 1, 3).astype(cache["v"].dtype))
            kb = jnp.take(ck, table, axis=0)   # (B, nbs, Hkv, block, D)
            vb = jnp.take(cv, table, axis=0)
            new_cache = {"k": ck, "v": cv, "pos": pos + s, "table": table}
            o = _ref_decode_paged(q, kb, vb, cfg, pos, causal)
            o = o.transpose(0, 2, 1, 3).reshape(b, s, h * dh)
            return L.linear(p["wo"], o), new_cache
        if jnp.ndim(pos) == 1:
            # per-slot write positions (continuous-batching slot pool): each
            # row appends at its OWN sequence offset — a vmapped row-wise
            # dynamic_update_slice, which lowers to a scatter that stays
            # local on the sequence-sharded cache
            def _row(c, u, p):
                return jax.lax.dynamic_update_slice(c, u, (0, p, 0))
            ck = jax.vmap(_row)(cache["k"], k.astype(cache["k"].dtype), pos)
            cv = jax.vmap(_row)(cache["v"], v.astype(cache["v"].dtype), pos)
        else:
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, 0, pos, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, 0, pos, 0))
        new_cache = {"k": ck, "v": cv, "pos": pos + s}
        k, v = ck, cv
        # dynamic offsets need the ref path's position masking; the Pallas
        # kernel takes a static python offset, so decode uses q_offset via
        # masking against positions below.
        o = _ref_decode(q, k, v, cfg, pos, causal)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, h * dh)
        return L.linear(p["wo"], o), new_cache

    o = flash_attention(q, k, v, causal=causal and x_kv is None,
                        window=cfg.window, softcap=cfg.softcap,
                        scale=cfg.scale, q_offset=q_offset, backend=backend)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, h * dh)
    return L.linear(p["wo"], o)


def _ref_decode(q, k, v, cfg: AttnConfig, pos, causal: bool):
    """Decode attention with a *traced* position offset: mask by absolute
    positions (cols <= pos + i, window, cap).  q: (B,H,Sq,D), k/v full cache.
    ``pos`` may be a scalar (static batch: every row at the same offset) or
    a (B,) vector (slot pool: each row masks against its OWN length)."""
    b, h, sq, d = q.shape
    hkv = k.shape[1]
    g = h // hkv
    scale = cfg.scale if cfg.scale is not None else d ** -0.5
    qg = q.reshape(b, hkv, g, sq, d)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if cfg.softcap is not None:
        s = cfg.softcap * jnp.tanh(s / cfg.softcap)
    per_row = jnp.ndim(pos) == 1
    q_pos = (pos[:, None] if per_row else pos) + jnp.arange(sq)
    k_pos = jnp.arange(k.shape[2])
    mask = jnp.ones(q_pos.shape + (k.shape[2],), bool)
    if causal:
        mask &= k_pos <= q_pos[..., None]
    if cfg.window is not None:
        mask &= k_pos > q_pos[..., None] - cfg.window
    if per_row:                              # (B, sq, skv) row-wise mask
        s = jnp.where(mask[:, None, None], s, -2.3819763e38)
    else:
        s = jnp.where(mask[None, None, None], s, -2.3819763e38)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return o.reshape(b, h, sq, d).astype(q.dtype)


def _ref_decode_paged(q, kb, vb, cfg: AttnConfig, pos, causal: bool):
    """Decode attention over per-row GATHERED blocks: q (B, H, Sq, D),
    kb/vb (B, nbs, Hkv, block, D) in table order, so the global position of
    entry (n, j) is ``n*block + j``.  Math is ``_ref_decode`` with the
    cache's sequence axis left factored as (blocks, block) — the softmax
    runs over both axes jointly, and under SPMD its cross-shard merge
    lowers to the same small all-reduces as the slot path (the block dim is
    replicated, the within-block dim is the sharded one)."""
    b, h, sq, d = q.shape
    nbs, hkv, bsz = kb.shape[1], kb.shape[2], kb.shape[3]
    g = h // hkv
    scale = cfg.scale if cfg.scale is not None else d ** -0.5
    qg = q.reshape(b, hkv, g, sq, d)
    kb = kb.transpose(0, 2, 1, 3, 4)          # (B, Hkv, nbs, block, D)
    vb = vb.transpose(0, 2, 1, 3, 4)
    s = jnp.einsum("bhgqd,bhnkd->bhgqnk", qg.astype(jnp.float32),
                   kb.astype(jnp.float32)) * scale
    if cfg.softcap is not None:
        s = cfg.softcap * jnp.tanh(s / cfg.softcap)
    q_pos = pos[:, None] + jnp.arange(sq)                 # (B, sq)
    k_pos = jnp.arange(nbs)[:, None] * bsz + jnp.arange(bsz)  # (nbs, block)
    mask = jnp.ones((b, sq, nbs, bsz), bool)
    if causal:
        mask &= k_pos[None, None] <= q_pos[..., None, None]
    if cfg.window is not None:
        mask &= k_pos[None, None] > q_pos[..., None, None] - cfg.window
    s = jnp.where(mask[:, None, None], s, -2.3819763e38)
    m = jnp.max(s, axis=(-2, -1), keepdims=True)
    e = jnp.exp(s - m)
    p = e / jnp.sum(e, axis=(-2, -1), keepdims=True)
    o = jnp.einsum("bhgqnk,bhnkd->bhgqd", p, vb.astype(jnp.float32))
    return o.reshape(b, h, sq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Sequence-parallel attention (DSP-1D): used by lm.py / encdec.py
# ---------------------------------------------------------------------------

def attention_sp(p, x, cfg: AttnConfig, *, sharder, backend: str = "pallas",
                 fused_switch: bool = True, causal: bool = True,
                 x_kv: Optional[jax.Array] = None,
                 positions: Optional[jax.Array] = None,
                 return_kv: bool = False):
    """Attention under DSP-1D sequence parallelism: enter sequence-sharded,
    dynamic-switch to head-sharded for the attention stage, switch back.
    ``fused_switch`` stacks q/k/v into one constraint => ONE all-to-all
    (the DSP primitive); unfused issues three (Ulysses schedule).
    Cross-attention (``x_kv``) head-shards the encoder K/V the same way.
    x: (B, S, C) -> (B, S, C)."""
    import jax.numpy as jnp  # local alias for clarity
    b, s, _ = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    src_kv = x if x_kv is None else x_kv
    s_kv = src_kv.shape[1]
    q = L.linear(p["wq"], x).reshape(b, s, h, dh)
    k = L.linear(p["wk"], src_kv).reshape(b, s_kv, hkv, dh)
    v = L.linear(p["wv"], src_kv).reshape(b, s_kv, hkv, dh)
    if cfg.qk_norm:
        q = L.rms_norm(p["q_norm"], q)
        k = L.rms_norm(p["k_norm"], k)
    if cfg.rope and x_kv is None:
        pos = positions if positions is not None else jnp.arange(s)
        q = L.apply_rope(q, pos, theta=cfg.rope_theta)
        k = L.apply_rope(k, pos, theta=cfg.rope_theta)

    kv_out = None
    if return_kv:   # decode-cache layout (B, Hkv, S, D), pre-replication
        kv_out = (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))

    sp = sharder.sp_size
    # The planned head-switch (Ulysses/DSP-1D) layout needs heads % SP == 0.
    # When heads don't divide the axis (gemma2: 8 heads on 16), fall back to
    # the kv-gather layout: Q/O stay *sequence*-sharded and the paper's
    # gather primitive is applied to K/V only — cheap under GQA (K/V is
    # Hkv/H of the activation) and free of any head-count constraint.
    head_switch = sharder.wants_head_switch(h)

    if head_switch and hkv < sp:
        rep = (sp + hkv - 1) // hkv              # replicate KV heads to SP
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
        hkv *= rep

    q = q.transpose(0, 2, 1, 3)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    is_causal = causal and x_kv is None
    if sharder.mesh is not None:
        # production path: chunked shard_map attention (no O(S^2) buffer).
        if not head_switch:
            o = chunked_attention(q, k, v, cfg, mesh=sharder.mesh,
                                  layout="kv_gather", causal=is_causal,
                                  backend=backend)
        else:
            if fused_switch and h == hkv and s == s_kv:
                qkv = sharder.heads_stacked(jnp.stack([q, k, v]))  # ONE a2a
                q, k, v = qkv[0], qkv[1], qkv[2]
            elif fused_switch:
                q = sharder.heads_enter(q)
                kv = sharder.heads_stacked(jnp.stack([k, v]))
                k, v = kv[0], kv[1]
            else:                                # Ulysses-style: 3 separate
                q = sharder.heads_enter(q)
                k = sharder.heads_enter(k)
                v = sharder.heads_enter(v)
            o = chunked_attention(q, k, v, cfg, mesh=sharder.mesh,
                                  layout="heads", causal=is_causal,
                                  backend=backend)
            o = sharder.heads(o)
    else:
        from repro.kernels.ops import flash_attention as _fa
        o = _fa(q, k, v, causal=is_causal, window=cfg.window,
                softcap=cfg.softcap, scale=cfg.scale, backend=backend)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, h * dh)
    o = L.linear(p["wo"], o)
    # switch back to the resid layout; as the mixer-exit boundary its
    # backward constrains the cotangent to the mixer's planned bwd layout
    o = sharder.mixer_exit3(o)
    if return_kv:
        return o, kv_out
    return o


# ---------------------------------------------------------------------------
# Chunked sharded attention: the production attention compute for long
# sequences.  A shard_map wraps a LOCAL query-chunked scan so the O(S^2)
# score matrix never materialises (flash-attention streaming semantics at the
# XLA level; on real TPU the local body calls the Pallas kernel instead).
# ---------------------------------------------------------------------------

def _largest_chunk(n: int, target: int = 512) -> int:
    c = min(n, target)
    while n % c:
        c -= 1
    return max(c, 1)


def _local_chunked_attn(q, k, v, cfg: AttnConfig, *, causal: bool,
                        q_offset, backend: str, chunk: int = 512,
                        score_budget: float = 512e6):
    """q: (B, H, Sq, D) local; k/v: (B, Hkv, Skv, D) local-full.
    Scan over Sq chunks; positions are global via q_offset (traced ok).
    The chunk adapts so the f32 score block (B*H*c*Skv) stays under
    ``score_budget`` bytes — the jnp analogue of sizing a flash kernel's
    q-block to VMEM."""
    b, h, sq, d = q.shape
    if backend == "pallas" and isinstance(q_offset, int):
        from repro.kernels.ops import flash_attention as _fa
        return _fa(q, k, v, causal=causal, window=cfg.window,
                   softcap=cfg.softcap, scale=cfg.scale, q_offset=q_offset)
    from repro.models import flags
    skv = k.shape[2]
    fit = max(int(score_budget // (b * h * skv * 4)), 16)
    c = _largest_chunk(sq, min(chunk, fit))
    nc = sq // c
    if nc == 1 or flags.FLAT_COST_MODE:
        return _ref_decode(q, k, v, cfg, q_offset, causal)
    qs = q.reshape(b, h, nc, c, d).transpose(2, 0, 1, 3, 4)   # (nc,B,H,c,D)

    import functools as _ft

    @_ft.partial(jax.checkpoint, prevent_cse=False)
    def one_chunk(qc, off):
        # remat per chunk: the backward recomputes this chunk's scores
        # instead of saving them — otherwise the scan stores the FULL
        # (B,H,S,S) f32 softmax across chunks (flash-attention bwd semantics)
        return _ref_decode(qc, k, v, cfg, off, causal)

    def body(i, qc):
        return i + 1, one_chunk(qc, q_offset + i * c)

    _, outs = jax.lax.scan(body, jnp.zeros((), jnp.int32), qs)
    return outs.transpose(1, 2, 0, 3, 4).reshape(b, h, sq, d)


def chunked_attention(q, k, v, cfg: AttnConfig, *, mesh, layout: str,
                      causal: bool, backend: str = "ref", chunk: int = 512):
    """Sharded chunked attention.

    layout:
      "heads"     q/k/v (B, H|Hkv, S, D) head-sharded over ``model``
                  (post dynamic-switch); full sequence local.
      "kv_gather" q (B, H, S, D) sequence-sharded; K/V replicated via the
                  in_spec (the all-gather IS the paper's gather primitive).
      "batch"     q/k/v (B', L, H, D) sharded on the folded batch dim over
                  every mesh axis (transformer2d stage attention).
    """
    from jax.sharding import PartitionSpec as P
    dp_axes = tuple(a for a in mesh.axis_names if a != "model")
    dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]

    if layout == "batch":
        spec = P((*dp_axes, "model") if len(dp_axes) else "model",
                 None, None, None)

        def body(ql, kl, vl):
            # (B'_loc, L, H, D) -> transpose to BHSD for the local kernel
            o = _local_chunked_attn(ql.transpose(0, 2, 1, 3),
                                    kl.transpose(0, 2, 1, 3),
                                    vl.transpose(0, 2, 1, 3),
                                    cfg, causal=causal, q_offset=0,
                                    backend=backend, chunk=chunk)
            return o.transpose(0, 2, 1, 3)

        fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                           out_specs=spec, check_vma=False)
        return fn(q, k, v)

    if layout == "heads":
        spec = P(dp, "model", None, None)

        def body(ql, kl, vl):
            return _local_chunked_attn(ql, kl, vl, cfg, causal=causal,
                                       q_offset=0, backend=backend,
                                       chunk=chunk)

        fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                           out_specs=spec, check_vma=False)
        return fn(q, k, v)

    if layout == "kv_gather":
        qspec = P(dp, None, "model", None)
        kvspec = P(dp, None, None, None)     # replicated = gathered K/V

        def body(ql, kl, vl):
            idx = jax.lax.axis_index("model")
            s_loc = ql.shape[2]
            return _local_chunked_attn(ql, kl, vl, cfg, causal=causal,
                                       q_offset=idx * s_loc, backend="ref",
                                       chunk=chunk)

        fn = jax.shard_map(body, mesh=mesh, in_specs=(qspec, kvspec, kvspec),
                           out_specs=qspec, check_vma=False)
        return fn(q, k, v)

    raise ValueError(layout)
