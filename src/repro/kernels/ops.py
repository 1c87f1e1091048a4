"""Jit-ready wrappers around the Pallas kernels.

Each op:
  * accepts model-layout tensors, pads to kernel block multiples,
  * dispatches to the Pallas kernel (interpret-mode on CPU, compiled on TPU)
    or to the pure-jnp reference (``backend="ref"``, used by the dry-run so
    XLA's cost model accounts the FLOPs),
  * defines a custom VJP whose backward recomputes through the reference —
    the standard scope-control trade on TPU when the forward is the hot spot.

Attention's forward picks its implementation from the shape: the flash
kernel only when the keys span more than one 128-key block (``KV_BLOCK``).
Within one such block flash has nothing to tile, so the kernel would only
pay for its grid and for padding the keys to the block; there the forward
runs as fused attention that XLA compiles, with the kernel's masking and
f32 softmax, under the scope ``tracing.ATTN_XLA``.  Past it,
``flash_tiles`` picks the kernel's tiles from the shape alone: lengths pad
to a multiple of 128 and each tile is the largest of 1024/512/256/128 rows
that divides its padded length.  Both of the kernel's dots take f32
operands at ``HIGHEST`` (``flash_attention``'s module notes).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import tracing
from repro.kernels import ref as _ref
from repro.kernels.flash_attention import MASK_VALUE, flash_attention_fwd
from repro.kernels.ssd_scan import ssd_scan_fwd


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

KV_BLOCK = 128      # keys up to one block run as XLA's fused attention
TILES = (1024, 512, 256, 128)


def flash_tiles(sq: int, skv: int) -> Tuple[int, int]:
    """(block_q, block_k) of the flash kernel for these lengths.

    Lengths pad to a multiple of 128, never further, and each tile is the
    largest of ``TILES`` that divides its padded length (a key tile that
    covers every key needs no online rescale).  A short query (decode:
    Sq <= 128) keeps one tile of Sq rounded to 8."""
    def largest(n):
        return next(t for t in TILES if _round_up(n, 128) % t == 0)
    return (_round_up(sq, 8) if sq <= 128 else largest(sq)), largest(skv)


@functools.lru_cache(maxsize=None)
def _make_attention(causal: bool, window: Optional[int],
                    softcap: Optional[float], scale: Optional[float],
                    q_offset: int, backend: str):
    """Build a custom-VJP attention fn for a static config (cached)."""

    def ref_fn(q, k, v):
        return _ref.attention_ref(q, k, v, causal=causal, window=window,
                                  softcap=softcap, scale=scale,
                                  q_offset=q_offset)

    def fwd_plain(q, k, v):
        if backend == "ref":
            return ref_fn(q, k, v)
        b, hq, sq, d = q.shape
        skv = k.shape[2]
        if skv <= KV_BLOCK:
            with tracing.scope(tracing.ATTN_XLA):
                return _fused_attention(q, k, v, causal=causal,
                                        window=window, softcap=softcap,
                                        scale=scale, q_offset=q_offset)
        bq, bk = flash_tiles(sq, skv)
        out = flash_attention_fwd(_pad_to(q, 2, bq), _pad_to(k, 2, bk),
                                  _pad_to(v, 2, bk), causal=causal,
                                  window=window, softcap=softcap,
                                  scale=scale, q_offset=q_offset,
                                  kv_len=skv, block_q=bq, block_k=bk)
        return out[:, :, :sq]

    @jax.custom_vjp
    def attn(q, k, v):
        return fwd_plain(q, k, v)

    def attn_fwd(q, k, v):
        return fwd_plain(q, k, v), (q, k, v)

    def attn_bwd(res, g):
        q, k, v = res
        with tracing.scope(tracing.ATTN_BWD):
            _, vjp = jax.vjp(ref_fn, q, k, v)
            return vjp(g)

    attn.defvjp(attn_fwd, attn_bwd)
    return attn


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _fused_attention(q, k, v, *, causal, window, softcap, scale, q_offset):
    """The flash kernel's forward over a single KV block, as two batched
    dots XLA fuses: the same products (bf16 operands are upcast exactly,
    f32 accumulation, f32 probabilities into p·v), the same masks, and a
    row with no key left gives zeros, as the kernel's does."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    g = hq // hkv
    scale = d ** -0.5 if scale is None else scale
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("bhgqd,bhkd->bhgqk", q.reshape(b, hkv, g, sq, d), k,
                   precision=hi, preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    rows = q_offset + jnp.arange(sq)[:, None]
    cols = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= cols <= rows
    if window is not None:
        mask &= cols > rows - window
    s = jnp.where(mask, s, MASK_VALUE)
    p = jnp.where(mask, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
    l = p.sum(-1, keepdims=True)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32),
                   precision=hi, preferred_element_type=jnp.float32)
    # l >= 1 where a key is left (the row's max adds exp(0)); a row with
    # none has l = 0 and o = 0, which stays 0
    o = o / jnp.maximum(l, 1.0)
    return o.reshape(b, hq, sq, d).astype(q.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = False, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None, q_offset: int = 0,
                    backend: str = "pallas") -> jax.Array:
    """Multi-head attention; q: (B, Hq, Sq, D), k/v: (B, Hkv, Skv, D).

    backend: "pallas" (kernel; interpret-mode off-TPU) or "ref" (pure jnp —
    used by the dry-run/roofline so XLA accounts the FLOPs).  Under
    "pallas" the flash kernel runs, on the tiles ``flash_tiles`` picks,
    when Skv > ``KV_BLOCK``; when every key fits in one such block (e.g.
    the DiT's temporal attention over T frames) the forward is XLA's fused
    attention with the same masks and precision.
    """
    fn = _make_attention(causal, window, softcap, scale, q_offset, backend)
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# Mamba-2 SSD scan
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _make_ssd(chunk: int, backend: str, has_skip: bool):

    def ref_fn(x, dt, a, b, c, d_skip=None):
        # vectorised chunked form: same math, no sequential scan, so XLA's
        # cost model sees every FLOP (the sequential ssd_ref remains the
        # test oracle)
        return _ref.ssd_chunked_ref(x, dt, a, b, c, d_skip=d_skip,
                                    chunk=chunk)

    def fwd_plain(x, dt, a, b, c, d_skip=None):
        if backend == "ref":
            return ref_fn(x, dt, a, b, c, d_skip)
        bs, l, h, p = x.shape
        ck = min(chunk, _round_up(l, 8))
        # kernel layout: (B, H, L, P) / (B, H, L) / (B, G, L, S)
        xdt = (x * dt[..., None]).transpose(0, 2, 1, 3)
        da = (dt * a[None, None, :]).transpose(0, 2, 1)
        bt = b.transpose(0, 2, 1, 3)
        ct = c.transpose(0, 2, 1, 3)
        lp = _round_up(l, ck)
        if lp != l:
            xdt = _pad_to(xdt, 2, ck)
            da = _pad_to(da, 2, ck)     # pad da with 0: exp(0)=1 decay, but
            bt = _pad_to(bt, 2, ck)     # xdt/b are 0 there so state unchanged
            ct = _pad_to(ct, 2, ck)
        y = ssd_scan_fwd(xdt, da, bt, ct, chunk=ck)
        y = y.transpose(0, 2, 1, 3)[:, :l]
        if d_skip is not None:
            y = y + d_skip[None, None, :, None] * x
        return y.astype(x.dtype)

    if has_skip:
        @jax.custom_vjp
        def op(x, dt, a, b, c, d_skip):
            return fwd_plain(x, dt, a, b, c, d_skip)

        def op_fwd(x, dt, a, b, c, d_skip):
            return fwd_plain(x, dt, a, b, c, d_skip), (x, dt, a, b, c, d_skip)

        def op_bwd(res, g):
            _, vjp = jax.vjp(lambda *args: ref_fn(*args), *res)
            return vjp(g)
    else:
        @jax.custom_vjp
        def op(x, dt, a, b, c):
            return fwd_plain(x, dt, a, b, c)

        def op_fwd(x, dt, a, b, c):
            return fwd_plain(x, dt, a, b, c), (x, dt, a, b, c)

        def op_bwd(res, g):
            _, vjp = jax.vjp(lambda *args: ref_fn(*args, None), *res)
            return vjp(g)

    op.defvjp(op_fwd, op_bwd)
    return op


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, d_skip: Optional[jax.Array] = None, *,
             chunk: int = 128, backend: str = "pallas") -> jax.Array:
    """Mamba-2 SSD.  x: (B, L, H, P), dt: (B, L, H), a: (H,),
    b/c: (B, L, G, S).  Returns y: (B, L, H, P)."""
    fn = _make_ssd(chunk, backend, d_skip is not None)
    if d_skip is not None:
        return fn(x, dt, a, b, c, d_skip)
    return fn(x, dt, a, b, c)
