"""Operations and bytes the algorithms need, from their shapes.

These are the work any implementation has to do, not what today's code
moves, so a faster implementation of the same step reads against the same
count.  Each function names the shapes it reads.
"""

from __future__ import annotations


def mpdata_step_bytes(rows: int, cols: int, bytes_per_cell: int = 4) -> int:
    """One MPDATA time step over a (rows, cols) block: the field read once
    and written once.  (Temporal blocking over several steps would make
    this count stale; a benchmark change would then correct it.)"""
    return 2 * rows * cols * bytes_per_cell


def decode_attn_cost(contexts, n_heads: int, n_kv_heads: int,
                     head_dim: int, kv_bytes: int = 2,
                     q_bytes: int = 2) -> tuple:
    """(flops, bytes) of one layer's decode attention over live slots.

    ``contexts`` holds, per live slot, the positions it attends (its live
    context, the new token included).  Bytes: K and V of those positions
    (n_kv_heads x head_dim each) plus q in and out (n_heads x head_dim).
    FLOPs: q.k and p.v, 2 x 2 x n_heads x head_dim per position.
    """
    ctx = sum(contexts)
    kv = 2 * ctx * n_kv_heads * head_dim * kv_bytes
    qo = 2 * len(contexts) * n_heads * head_dim * q_bytes
    flops = 4 * ctx * n_heads * head_dim
    return flops, kv + qo


def dense_layer_params(d_model: int, n_heads: int, n_kv_heads: int,
                       head_dim: int, d_ff: int) -> int:
    """Matrix parameters of one Llama block: q, k, v, o and the gated
    MLP's three matrices (norm scales are not matrix work)."""
    attn = d_model * head_dim * (2 * n_heads + 2 * n_kv_heads)
    return attn + 3 * d_model * d_ff


def decoder_token_flops(m: dict, position: int, with_head: bool) -> int:
    """Model FLOPs of one token at ``position`` (0-based) through the
    decoder ``m`` (n_layers, d_model, n_heads, n_kv_heads, head_dim, d_ff,
    vocab_size): 2 x matrix params, attention over position + 1 keys
    (2 x 2 x n_heads x head_dim each, per layer), and the output head when
    this token's logits are used."""
    per_layer = 2 * dense_layer_params(m["d_model"], m["n_heads"],
                                       m["n_kv_heads"], m["head_dim"],
                                       m["d_ff"])
    attn = 4 * (position + 1) * m["n_heads"] * m["head_dim"]
    head = 2 * m["d_model"] * m["vocab_size"] if with_head else 0
    return m["n_layers"] * (per_layer + attn) + head
