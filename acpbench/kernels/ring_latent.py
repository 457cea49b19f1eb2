"""The sliding layers' walk over a ring of latent rows (the leaf
`ring_latent`): the bytes it must read and the operations it must do.

One decode step reads, per sliding layer and lane, the latent rows its query
sees: the last `window` rows of the context, its own among them, or the whole
context while that is shorter: `min(len + 1, window)` rows of `swa_kv_lora_rank
+ swa_qk_rope_head_dim` values (1,088 at the published sizes: 2,176 B), one
row for all heads. The rows' values, not the 1,152 the chip stores them on nor
the whole ring (34 pages of 16 rows, which a gather of the ring reads at any
context). Against each row every query head takes a score over the whole row
and a value product over its first `swa_kv_lora_rank` columns: `2 * heads *
(1,088 + 1,024)` operations a row, 124 a byte at 64 heads: bound by bytes.
The lane's own new row is counted though it is not read from the ring; by
this count no reading can pass 100%.
"""

from __future__ import annotations


def rows_read(seq_len: int, window: int) -> int:
    """Rows a sliding layer's attention covers for a lane with `seq_len` rows cached: they and its own, in the window."""
    return min(int(seq_len) + 1, window)


def bytes_per_step(seq_lens, *, window: int, row_values: int, n_layers: int, bytes_per_element: int = 2) -> int:
    """HBM bytes one decode step's ring walks must read on one chip, over the `n_layers` sliding layers."""
    return sum(rows_read(n, window) for n in seq_lens if n > 0) * row_values * bytes_per_element * n_layers


def flops_per_step(seq_lens, *, window: int, heads: int, row_values: int, value_width: int, n_layers: int) -> int:
    """q~ . row and p . row[:value_width]: 2 * heads * (row_values + value_width) a row and layer."""
    return sum(rows_read(n, window) for n in seq_lens if n > 0) * 2 * heads * (row_values + value_width) * n_layers
