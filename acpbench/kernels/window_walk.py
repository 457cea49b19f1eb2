"""The window layers' walk (`paged_window_walk`): the bytes it must read.

One decode step reads, per window layer, the pages of a slot's ring that
hold the rows its query sees: the last `window` rows of the context, its own
among them, or the whole context while that is shorter: `min(len, window)`
rows rounded up to whole pages, of K and of V, each row `kv_heads *
head_dim` elements. Only those pages count: the page the window's edge
lies in is read whole and its rows before the edge are masked, which is the
kernel's own cost and lowers the share (one page in 65 at the published
sizes). Bound by bytes, as the full layers' walk is (`page_walk.py`).
"""

from __future__ import annotations


def rows_read(seq_len: int, window: int) -> int:
    """Rows a window layer's attention covers for a context of `seq_len`."""
    return min(int(seq_len), window)


def bytes_per_step(seq_lens, *, window: int, page_size: int, kv_heads: int, head_dim: int,
                   n_layers: int, bytes_per_element: int = 2) -> int:
    """HBM bytes one decode step's window walks must read on one chip, over
    the `n_layers` window layers."""
    pages = sum(-(-rows_read(n, window) // page_size) for n in seq_lens if n > 0)
    return pages * page_size * kv_heads * head_dim * bytes_per_element * 2 * n_layers


def flops_per_step(seq_lens, *, window: int, heads: int, head_dim: int, n_layers: int) -> int:
    """q.k and p.v: 2 * 2 * rows * heads * head_dim per sequence and layer."""
    return sum(4 * rows_read(n, window) * heads * head_dim for n in seq_lens if n > 0) * n_layers
