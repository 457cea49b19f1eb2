"""The walk over chosen LATENT rows (the leaf `sparse_latent`: the fetch of
the latent rows the indexer chose and the absorbed attention over them): the
bytes it must read and the operations it must do.

One decode step reads, per full layer and lane, the latent row of each CHOSEN
position: `min(len + 1, topk)` rows of `kv_lora_rank + qk_rope_head_dim`
values (576 at the published sizes: 1,152 B), one row for ALL heads, whatever
pages they lie in. The row's values, not the 640 the chip stores them on.
Against each chosen row every query head takes a score over the whole row
and a value product over its first `kv_lora_rank` columns: `2 * heads * (576
+ 512)` operations a row, 278,528 at 128 heads: 241 a byte of bfloat16 rows,
on the chip's ridge of 240, where a walk over chosen K and V rows of heads is
at 8: this leaf is bound by either, so the reader takes the greater. The
lane's own new row is among the candidates and is counted when chosen though
it is not read from the pool; by this count no reading can pass 100%.
"""

from __future__ import annotations


def chosen(seq_lens, topk: int) -> int:
    """Rows a step attends over: a lane sees its cached rows and its own."""
    return sum(min(int(n) + 1, topk) for n in seq_lens if n > 0)


def bytes_per_step(seq_lens, *, topk: int, row_values: int, n_layers: int, bytes_per_element: int = 2) -> int:
    """HBM bytes one decode step's walks over chosen latent rows must read on one chip."""
    return chosen(seq_lens, topk) * row_values * bytes_per_element * n_layers


def flops_per_step(seq_lens, *, topk: int, heads: int, row_values: int, value_width: int, n_layers: int) -> int:
    """q~ . row and p . row[:value_width]: 2 * heads * (row_values + value_width) a chosen row and layer."""
    return chosen(seq_lens, topk) * 2 * heads * (row_values + value_width) * n_layers
