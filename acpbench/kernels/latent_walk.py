"""The latent walk (`paged_latent_walk`): the bytes it must read and the
operations it must do.

One decode step reads, per layer, the pages that hold a slot's latent rows:
its context rounded up to whole pages, ONE read a page (the row is key and
value at once: `kv_lora_rank + qk_rope_head_dim` values, the least row, not
the width the chip's tiling stores it on). Against each row every query
head takes one product over the whole row (the score) and one over its
first `kv_lora_rank` values (the value): `2 * heads * (row + latent)`
operations a row, 60 a byte at the published sizes where the K/V walks have
4 to 20, so this walk is held to the greater of its bytes over the HBM peak
and its operations over the bf16 peak. By this count no reading can pass
100%: the rows counted are those that must be read, and the kernel's masked
rows of a last page, its second pass over `p` and its self term are its own.
"""

from __future__ import annotations


def bytes_per_step(seq_lens, *, page_size: int, row_values: int, n_layers: int, bytes_per_element: int = 2) -> int:
    """HBM bytes one decode step's latent walks must read on one chip."""
    pages = sum(-(-int(n) // page_size) for n in seq_lens if n > 0)
    return pages * page_size * row_values * bytes_per_element * n_layers


def flops_per_step(seq_lens, *, heads: int, row_values: int, latent_values: int, n_layers: int) -> int:
    """q.row and p.row[:latent]: 2 * heads * (row + latent) a live row and layer."""
    return sum(2 * heads * (row_values + latent_values) * int(n) for n in seq_lens if n > 0) * n_layers
