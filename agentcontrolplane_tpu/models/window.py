"""Window layers beside full layers: a slot's ring of pages and what the
window layers count on the device (``mellum``, ``exaone``, ``dots``; the ring's
pages, positions and walks are ``ops/paged.py``'s ``ring_*``). A mechanism
module (``docs/serving-engine.md``, "Adding a family"): it imports ``ops/``
alone, and an edit here is an edit to those three families' cells.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..ops.paged import ring_size

WINDOW_COUNTS = 4  # dispatches, rows read, rows with no window, lanes past the window


def slot_ring(leaf, window: int) -> tuple[int, int]:
    """(pages of a ring, the slot whose ring nothing reads) of the rings'
    leaf ``[layers, (slots + 1) * ring, P, ...]``."""
    ring = ring_size(window, leaf.shape[2])
    return ring, leaf.shape[1] // ring - 1


def window_counts(window: int, positions, valid):
    """What the window layers' attention covers over the queries at
    ``positions`` [B, T] (``valid`` [B, T]), one layer's: rows read, rows
    there would be with no window, and the rows (a decode step: the lanes)
    whose query lies past the window."""
    seen = jnp.where(valid, positions + 1, 0).astype(jnp.uint32)
    return jnp.stack([
        jnp.ones((), jnp.uint32), jnp.sum(jnp.minimum(seen, window)), jnp.sum(seen),
        jnp.sum((jnp.max(seen, axis=1) > window).astype(jnp.uint32)),
    ])


def describe_window(total, at: int, window: int, window_layers: int, full_layers: int, **sizes) -> dict:
    """``Engine.stats()["window"]`` from the counters summed by the engine,
    the window layers' from column ``at`` on, decode steps and prefills apart:
    ``steps`` dispatches, ``rows_read`` the rows one window layer's attention
    covered over their queries (the query's own among them),
    ``rows_unwindowed`` what it would have covered with no window,
    ``slots_past_window`` the lanes (rows of a prefill) whose last query lay
    past the window. ``sizes``: what else a family says of its rows."""
    def row(r):
        return {"steps": int(r[at]), "rows_read": int(r[at + 1]), "rows_unwindowed": int(r[at + 2]),
                "slots_past_window": int(r[at + 3])}

    return {"window": {"window": window, "window_layers": window_layers, "full_layers": full_layers, **sizes,
                       "decode": row(total[0]), "prefill": row(total[1])}}
