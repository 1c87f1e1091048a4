"""Operations and bytes the algorithm needs, from shapes alone, and the
chips' peaks.

Model FLOPs of a training step count each multiply-add as 2 and the
backward as twice the forward (3x in all); they leave out recomputation.
Per token: patch embedding, the q/k/v/o projections and the two FFN
matrices of every block, and the head.  Per sample: the timestep
projection and each block's adaLN modulation, which act on the (B, C)
timestep embedding and not on every token.  Attention: q k^T and p v at
the unpadded lengths, over S in spatial blocks and over T in temporal ones.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; a kind not in the table is an
    error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}")
    return table[device_kind]


def attention_flops(seqs: int, length: int, heads: int, head_dim: int):
    """q k^T and p v for ``seqs`` sequences of ``length`` tokens."""
    return 4 * seqs * heads * length * length * head_dim


def forward_flops(m, batch: int, temporal: int, spatial: int) -> float:
    """Model FLOPs of one forward pass; ``m`` has the configuration's sizes
    (``n_layers`` blocks alternating spatial and temporal)."""
    d, hd = m.d_model, m.n_heads * m.head_dim
    tokens = batch * temporal * spatial
    per_block_token = 2 * (4 * d * hd + 2 * d * m.d_ff)
    per_token = 2 * 2 * m.in_dim * d + m.n_layers * per_block_token
    per_sample = 2 * d * d + m.n_layers * 2 * d * 6 * d
    pairs = m.n_layers // 2
    attn = pairs * (
        attention_flops(batch * temporal, spatial, m.n_heads, m.head_dim)
        + attention_flops(batch * spatial, temporal, m.n_heads, m.head_dim))
    return tokens * per_token + batch * per_sample + attn


def train_step_flops(m, batch: int, temporal: int, spatial: int) -> float:
    return 3 * forward_flops(m, batch, temporal, spatial)


def flash_forward_cost(seqs: int, length: int, heads: int, head_dim: int,
                       itemsize: int):
    """(FLOPs, bytes) that one attention forward over ``seqs`` sequences of
    ``length`` needs at least: its two products, and reading q, k, v and
    writing o once each."""
    return (attention_flops(seqs, length, heads, head_dim),
            4 * seqs * heads * length * head_dim * itemsize)
