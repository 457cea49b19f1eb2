"""The walk over chosen rows (the leaf `sparse_walk`: the fetch of the rows
the indexer chose and the attention over them): the bytes it must read and
the operations it must do.

One decode step reads, per layer and lane, the K row and the V row of each
CHOSEN position: `min(len + 1, topk)` rows of `kv_heads * head_dim` values
each, whatever pages they lie in: `chosen x 2 x 512 values x 2 B` at the
published sizes. The least row, not a page: at one row in eight to thirteen
chosen nearly every page holds one, so a walk by pages would read what a
dense walk reads, and by this count it would read 12% to 17%. Against each
chosen row every query head takes a score and a value product: `4 * heads *
head_dim` operations a row, 8 a byte of bfloat16 rows at 32 query heads over
4 KV heads, under the chip's 240 a byte: bound by bytes. The lane's own new
row is among the candidates and is counted when chosen though it is not read
from the pool; by this count no reading can pass 100%.
"""

from __future__ import annotations


def chosen(seq_lens, topk: int) -> int:
    """Rows a step attends over: a lane sees its cached rows and its own."""
    return sum(min(int(n) + 1, topk) for n in seq_lens if n > 0)


def bytes_per_step(seq_lens, *, topk: int, kv_heads: int, head_dim: int, n_layers: int,
                   bytes_per_element: int = 2) -> int:
    """HBM bytes one decode step's walks over chosen rows must read on one chip."""
    return chosen(seq_lens, topk) * 2 * kv_heads * head_dim * bytes_per_element * n_layers


def flops_per_step(seq_lens, *, topk: int, heads: int, head_dim: int, n_layers: int) -> int:
    """q.k and p.v: 2 * 2 * heads * head_dim a chosen row and layer."""
    return chosen(seq_lens, topk) * 4 * heads * head_dim * n_layers
