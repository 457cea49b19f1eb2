"""Recurrent state beside the pages: a slot's tree ``{"ssm", "conv"}`` under
the cache's ``"state"`` with its snapshot ``"snap"`` and ``"counters"``,
whatever the leaves' ranks (``jamba``: the recurrence's float32 ``h`` and the
conv columns; ``nemotron_h``: ``S`` of heads in the order its kernels read).
The seam's ``install_state``, ``saved_state`` and ``counters``
(``models/__init__.py`` ``_with_state``) and what a prefill's commit writes. A
mechanism module (``docs/serving-engine.md``, "Adding a family"): it imports
``ops/`` alone, and an edit here is an edit to those two families' cells.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..observability import scopes


def conv_at(u_ext, rel, n: int):
    """The n columns of u before token ``rel`` [B] of each row -> [B, n * d_inner]."""
    T = u_ext.shape[1] - n
    idx = jnp.clip(rel, 0, T)[:, None] + jnp.arange(n)[None, :]
    got = jnp.take_along_axis(u_ext, idx[:, :, None], axis=1)
    return got.reshape(got.shape[0], -1)


def state_in(cache, slots, starts):
    """(ssm, conv) before the rows: zeros for a row that starts the
    sequence, the slot's state otherwise."""
    st = cache["state"]
    with scopes.layer("commit"):
        slots = jnp.clip(slots, 0, st["ssm"].shape[1] - 1)
        began = starts > 0
        return tuple(jnp.where(began[(None, slice(None)) + (None,) * (st[name].ndim - 2)], st[name][:, slots], 0)
                     for name in ("ssm", "conv"))


def commit_state(cache, pages, slots, ends, snaps, snap_ok, counts):
    """The cache with its pages replaced and the rows' state written: a
    row's end state always, its snapshot where one fell inside the row. A
    padding row names the last slot, which nothing reads."""
    st = cache["state"]
    with scopes.layer("commit"):
        slots = jnp.clip(slots, 0, st["ssm"].shape[1] - 1)
        out = {"snap": {}, "counters": st["counters"].at[1].add(counts)}
        for name in ("ssm", "conv"):
            out[name] = st[name].at[:, slots].set(ends[name].astype(st[name].dtype))
            old = st["snap"][name][:, slots]
            ok = snap_ok.reshape((1, -1) + (1,) * (old.ndim - 2))
            out["snap"][name] = st["snap"][name].at[:, slots].set(jnp.where(ok, snaps[name].astype(old.dtype), old))
        return {**pages, "state": out}


def install_state(cache: dict, slot, state: dict) -> dict:
    """``state["ssm" | "conv"][:, slot]`` = the tree ``state`` (a layer's
    leaves without the slot axis): what a continuation that starts past 0 in
    ``slot`` resumes from (a prefix entry's, a parked turn's or a host
    entry's saved state)."""
    st = cache["state"]
    put = lambda a, s: jax.lax.dynamic_update_slice(  # noqa: E731
        a, s.astype(a.dtype)[:, None], (0, slot) + (0,) * (a.ndim - 2))
    return {**cache, "state": {**st, "ssm": put(st["ssm"], state["ssm"]), "conv": put(st["conv"], state["conv"])}}


def saved_state(cache: dict, slot) -> dict:
    """A copy of the slot's snapshot, as the tree ``install_state`` takes."""
    take = lambda a: jax.lax.dynamic_slice(  # noqa: E731
        a, (0, slot) + (0,) * (a.ndim - 2), (a.shape[0], 1) + a.shape[2:])[:, 0]
    return jax.tree_util.tree_map(take, cache["state"]["snap"])


def counters(cache: dict) -> jax.Array:
    return cache["state"]["counters"]
