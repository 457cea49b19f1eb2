"""The walks of a verify-and-draft step (`paged_page_walk` and
`paged_window_walk` under `models/exaone.py`'s `verify_step_paged`): the
bytes the step NEEDS.

A step runs two query rows a lane over one table in each cache, and the MTP
block's rows over its own layer of the full pool. What it needs from HBM is
each lane's rows ONCE a layer: per window layer the pages that hold the last
`window` rows of the context (`window_walk.py`'s count), per full-attention
cache layer (the stack's full layers and the MTP block's) the lane's live
pages (`page_walk.py`'s count), K and V. A program that fetches a lane's
pages once for each of its rows (two lanes of the one-row walks over one
table, as the first version does) reads twice that and its share stands
under 50%: folding both rows into one fetch reads as the gain it is.
"""

from __future__ import annotations

from . import page_walk, window_walk


def bytes_per_step(seq_lens, *, window: int, page_size: int, kv_heads: int, head_dim: int, window_layers: int,
                   full_layers: int, bytes_per_element: int = 2) -> int:
    """HBM bytes one step's walks must read on one chip: `window_layers`
    rings and `full_layers` page lists (the MTP block's among them)."""
    common = dict(page_size=page_size, kv_heads=kv_heads, head_dim=head_dim, bytes_per_element=bytes_per_element)
    return (window_walk.bytes_per_step(seq_lens, window=window, n_layers=window_layers, **common)
            + page_walk.bytes_per_step(seq_lens, n_layers=full_layers, **common))
