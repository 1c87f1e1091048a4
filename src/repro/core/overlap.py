"""Comm-compute overlap: the planned switch decomposed into per-shard
``ppermute`` chunks, plus the shared ring-rotation helper.

Two things live here:

* ``ring_stream`` — the chunk/rotate/fold loop that ``core.ring``
  (K/V block rotation) and ``models.lm.sharded_embed`` (vocab-table chunk
  rotation) both execute.  One hop of ``jax.lax.ppermute`` per step, the
  held block at step ``t`` being the one device ``(idx - t) % n`` owns.

* ``overlapped_switch`` — the stage-boundary all-to-all of
  ``core.dsp.dynamic_switch`` decomposed into ``n-1`` independent per-shard
  ``ppermute`` hops, collective-matmul style.  Hop ``t`` sends the local
  chunk addressed to peer ``(idx + t) % n`` and receives source-shard
  ``(idx - t) % n`` of the device's own target slice; because no hop
  depends on another, the scheduler is free to keep every transfer in
  flight while the surrounding kernel (flash attention, projections)
  computes — and with a ``consume`` callback the next stage's per-shard
  prologue runs on shard ``i`` while shard ``i+1`` streams.  Bitwise
  identical to the one-shot all-to-all; per-device wire volume is the same
  ``(n-1)/n · M/n`` (each hop moves ``M/n²``).

``core.schedule.ScheduleExecutor`` threads this in as the opt-in
``overlap="chunked" | "double_buffer"`` executor mode; ``core.plan`` prices
boundaries under overlap by their EXPOSED seconds
(``max(comm, compute) - compute`` — ``core.topology.Topology
.exposed_seconds``).  docs/architecture.md §4 "Hiding the switch".
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp


# executor overlap modes (None = synchronous one-shot all-to-all)
OVERLAP_MODES = (None, "chunked", "double_buffer")


# ---------------------------------------------------------------------------
# Shared ring rotation (ring attention / vocab-sharded embedding)
# ---------------------------------------------------------------------------

def ring_stream(blocks, carry, fold: Callable, *,
                axis_name: str = "model", steps: Optional[int] = None,
                unroll: bool = False):
    """Rotate ``blocks`` one ring hop per step while folding each held block
    into ``carry``.

    At step ``t`` the held block is the one device ``(idx - t) % n``
    contributed; ``fold(t, src, blocks, carry) -> carry`` consumes it.  The
    rotation happens AFTER the fold, every step including the last — n hops
    move exactly the blocks' full global bytes (the Table-3 ring volume the
    benchmarks measure).  ``carry`` leaves must already be vma-varying over
    ``axis_name`` under shard_map (``jax.lax.pcast(..., to="varying")``); constants are fine as
    blocks.

    Args:
      blocks: pytree of per-device blocks to rotate (K/V shards, a vocab
        table chunk, ...).
      carry: pytree accumulated across steps.
      fold: ``(t, src, blocks, carry) -> carry`` with ``src`` the owner of
        the currently-held blocks (a traced index).
      axis_name: the ring mesh axis.
      steps: number of fold steps (defaults to the axis size).
      unroll: python-unroll the loop (compact HLO for tiny rings; the
        default ``fori_loop`` keeps HLO size flat in n).
    Returns:
      the folded carry.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    steps = n if steps is None else steps
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(t, state):
        blks, c = state
        src = (idx - t) % n
        c = fold(t, src, blks, c)
        blks = jax.tree_util.tree_map(
            lambda b: jax.lax.ppermute(b, axis_name, perm), blks)
        return blks, c

    if unroll:
        state = (blocks, carry)
        for t in range(steps):
            state = body(t, state)
        _, carry = state
    else:
        _, carry = jax.lax.fori_loop(0, steps, body, (blocks, carry))
    return carry


# ---------------------------------------------------------------------------
# Chunked / double-buffered switch (the overlapped stage boundary)
# ---------------------------------------------------------------------------

def overlapped_switch(x: jax.Array, src: int, tgt: int,
                      axis_name: str = "model", *,
                      mode: str = "chunked",
                      consume: Optional[Callable] = None) -> jax.Array:
    """``core.dsp.dynamic_switch`` decomposed into ``n-1`` per-shard
    ``ppermute`` hops — the overlapped stage boundary.

    The local array (dim ``src`` holding this device's shard, dim ``tgt``
    full) is cut into ``n`` chunks along ``tgt``; hop ``t`` sends chunk
    ``(idx + t) % n`` to peer ``(idx + t) % n`` and receives source-shard
    ``(idx - t) % n`` of the device's own target slice.  No hop depends on
    another, so every transfer can be in flight while the adjacent kernel
    computes; the result is bitwise identical to the one-shot tiled
    all-to-all.

    ``mode``:
      * ``"chunked"`` — each received shard is merged into the output as it
        lands (a chain of cheap update-slices: hop ``t+1``'s transfer
        overlaps hop ``t``'s merge and the surrounding kernel).
      * ``"double_buffer"`` — all hops stage into an ``(n, ...)`` receive
        buffer with NO inter-hop dependencies; one reshape assembles it
        when the consumer needs it.  Nothing serialises the transfers, so
        in a scanned body they slide earliest in the schedule — the variant
        that hides the next boundary's switch behind the current period's
        compute.

    ``consume`` (optional): ``consume(shard, t) -> shard`` applied to each
    source-shard as it arrives (hop 0 = the locally-kept chunk, no comm) —
    the collective-matmul hook: run the next stage's per-shard, token-local
    prologue (projections, norms) on shard ``i`` while shard ``i+1``
    streams.  The assembled result concatenates the consumed shards.
    """
    if mode not in ("chunked", "double_buffer"):
        raise ValueError(f"overlapped_switch mode {mode!r} not in "
                         f"('chunked', 'double_buffer')")
    if src == tgt:
        return x
    n = jax.lax.axis_size(axis_name)
    if x.shape[tgt] % n:
        raise ValueError(
            f"overlapped_switch: dim {tgt} (size {x.shape[tgt]}) "
            f"not divisible by SP size {n}")
    if n == 1:
        return consume(x, 0) if consume is not None else x
    idx = jax.lax.axis_index(axis_name)
    c = x.shape[tgt] // n
    blk = x.shape[src]

    def shard(t):
        """Source-shard ``(idx - t) % n`` of this device's target slice:
        hop 0 is the locally-kept chunk, hop t a single ppermute."""
        piece = jax.lax.dynamic_slice_in_dim(
            x, ((idx + t) % n) * c, c, axis=tgt)
        if t:
            perm = [(i, (i + t) % n) for i in range(n)]
            piece = jax.lax.ppermute(piece, axis_name, perm)
        if consume is not None:
            piece = consume(piece, t)
        return piece

    pieces = [shard(t) for t in range(n)]
    out_shape = list(pieces[0].shape)
    out_shape[src] = out_shape[src] * n

    if mode == "double_buffer":
        # stage every hop into one receive buffer; assemble with a single
        # gather ordered by source shard — hops stay mutually independent
        buf = jnp.stack(pieces, axis=0)                  # (n, ..., blk, ...)
        # output block p came in on hop (idx - p) % n (an involution: the
        # same map sends hop t to its source shard)
        buf = jnp.take(buf, (idx - jnp.arange(n)) % n, axis=0)
        return jnp.moveaxis(buf, 0, src).reshape(out_shape)

    # chunked: merge each shard into place as it lands
    out = jnp.zeros(out_shape, pieces[0].dtype)
    pb = pieces[0].shape[src]
    for t, piece in enumerate(pieces):
        pos = (idx - t) % n
        out = jax.lax.dynamic_update_slice_in_dim(
            out, piece, pos * pb, axis=src)
    return out


__all__ = ["ring_stream", "overlapped_switch", "OVERLAP_MODES"]
