"""The indexer's scores (the leaf `index_scores`): the bytes it must read and
the operations it must do.

One decode step reads, per layer and lane, the indexer's key of EVERY cached
row: `len` rows of `index_head_dim` values, `context rows x 64 values x 2 B`
at the published sizes. The least row, not the width the chip stores it on (a
128-lane tile: twice that) nor the lane's whole table. Against each row every
indexer head takes one product, a ReLU and a weighted sum: `2 * index_heads *
index_head_dim` operations a row, 16 a byte, bound by bytes. By this count no
reading can pass 100%.
"""

from __future__ import annotations


def bytes_per_step(seq_lens, *, index_head_dim: int, n_layers: int, bytes_per_element: int = 2) -> int:
    """HBM bytes one decode step's index scores must read on one chip."""
    return sum(int(n) for n in seq_lens if n > 0) * index_head_dim * bytes_per_element * n_layers


def flops_per_step(seq_lens, *, index_heads: int, index_head_dim: int, n_layers: int) -> int:
    """qI_j . kI_s over the heads: 2 * index_heads * index_head_dim a cached row and layer."""
    return sum(int(n) for n in seq_lens if n > 0) * 2 * index_heads * index_head_dim * n_layers
