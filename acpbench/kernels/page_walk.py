"""Paged decode attention (the page walk): the bytes it must read.

One decode step reads, per layer, the live KV pages of every active
sequence: ceil(len / page) pages of K and of V, each page * kv_heads *
head_dim elements. Only live pages count: what the program moves beside
them (whole-pool slices, relayouts) is its own cost and lowers the share.
The walk is bound by bytes: its operations (4 * len * heads * head_dim per
sequence) stand at under 1 FLOP a byte of bf16 pages * n_rep.
"""

from __future__ import annotations


def bytes_per_step(seq_lens, *, page_size: int, kv_heads: int, head_dim: int,
                   n_layers: int, bytes_per_element: int = 2) -> int:
    """HBM bytes one decode step's walks must read on one chip holding
    `kv_heads` KV heads, over all layers."""
    pages = sum(-(-int(n) // page_size) for n in seq_lens if n > 0)
    return pages * page_size * kv_heads * head_dim * bytes_per_element * 2 * n_layers


def flops_per_step(seq_lens, *, heads: int, head_dim: int, n_layers: int) -> int:
    """q.k and p.v: 2 * 2 * len * heads * head_dim per sequence and layer."""
    return sum(4 * int(n) * heads * head_dim for n in seq_lens if n > 0) * n_layers
