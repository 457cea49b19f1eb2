"""A dispatch's per-lane scalars as one int32 buffer.

What the host hands a program for one dispatch, besides the token rows and
the page ids, is a handful of numbers a lane: lengths, sampling parameters,
constraint states, budgets. Each used to be its own ``device_put`` of 32 to
128 bytes, and the device idled while the host made them one after another.
A ``LaneRows`` names those numbers as the rows of one ``[rows, width]``
int32 array: the host packs it (float32 rows bit-cast, booleans as 0/1) and
uploads it once, and the jitted wrapper unpacks it with static row slices
before it calls the model, so the models and kernels never see it.

The dispatch counter rides the same buffer (row ``n``, the same value in
every lane): a program derives its sampling key from the engine's one
device-resident base key and that counter (``dispatch_key``), so no key is
split on the host. The decode program hands the buffer back as its carry
with ``chain`` one higher: a block that nothing dirtied feeds the carry
back and draws from ``(n, chain + 1)``, a key no other dispatch has.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_KINDS = {"i": np.int32, "f": np.float32, "b": np.bool_}


class LaneRows:
    """An ordered set of named rows; ``kinds`` maps a row's name to ``"i"``
    (int32), ``"f"`` (float32, bit-cast) or ``"b"`` (bool, 0/1)."""

    def __init__(self, **kinds: str):
        self.kinds = {name: np.dtype(_KINDS[k]) for name, k in kinds.items()}
        self.index = {name: i for i, name in enumerate(kinds)}

    def pack(self, width: int, **rows) -> np.ndarray:
        """The host side: every row given, a scalar standing for all lanes."""
        if rows.keys() != self.kinds.keys():
            raise ValueError(
                f"lanes {sorted(rows)} given for rows {sorted(self.kinds)}"
            )
        out = np.zeros((len(self.kinds), width), dtype=np.int32)
        for name, kind in self.kinds.items():
            # a float row keeps its bits: the int32 row viewed as float32
            out[self.index[name]].view(np.int32 if kind == np.bool_ else kind)[:] = (
                np.asarray(rows[name], dtype=kind)
            )
        return out

    def unpack(self, lanes: jax.Array) -> dict:
        """The program side (traced): each row in its own dtype."""
        out = {}
        for name, kind in self.kinds.items():
            row = lanes[self.index[name]]
            if kind == np.float32:
                row = jax.lax.bitcast_convert_type(row, jnp.float32)
            elif kind == np.bool_:
                row = row != 0
            out[name] = row
        return out

    def update(self, lanes: jax.Array, **rows) -> jax.Array:
        """``lanes`` with the given rows replaced (traced; int and bool rows)."""
        for name, row in rows.items():
            lanes = lanes.at[self.index[name]].set(row.astype(jnp.int32))
        return lanes


_SAMPLING = dict(temps="f", top_ks="i", top_ps="f", con_states="i", constrained="b", budgets="i")

# prefill, continuation and KV-only chunk dispatches, both layouts; `slots`
# and `snap_at` are read by the slot layout and by a family with per-slot
# state, `starts` by a continuation
PREFILL = LaneRows(n="i", lengths="i", starts="i", slots="i", snap_at="i", **_SAMPLING)
# the decode block's carry: what the program advances and what it only reads
DECODE = LaneRows(n="i", chain="i", tokens="i", seq_lens="i", active="b", **_SAMPLING)
# the speculative verify dispatch
VERIFY = LaneRows(n="i", n_input="i", starts="i", active="b", force_reject="b", **_SAMPLING)


_CHAIN_MIX = np.uint32(0x9E3779B1)  # odd: chain -> chain * mix is one to one mod 2**32


def dispatch_key(base: jax.Array, n: jax.Array, chain=None) -> jax.Array:
    """The key of dispatch ``n`` (a lanes row; lane 0 is read), and of the
    ``chain``-th clean decode block after it: the base key with ``n`` XOR-ed
    into its first word and ``chain``, spread by an odd multiplier, into its
    second. Distinct ``(n, chain)`` give distinct keys of one base key, and a
    keyed generator's streams under distinct keys are what distinct seeds
    give (``jax.random.key(seed)`` is ``[0, seed]``). Not ``fold_in``: its
    unrolled cipher doubled the time every program takes to lower on the
    chip's host (PERF.md, PR 36), which is set-up time in every cell."""
    mix = jnp.zeros_like(jax.random.key_data(base)).at[0].set(n[0].astype(jnp.uint32))
    if chain is not None:
        mix = mix.at[1].set(chain[0].astype(jnp.uint32) * _CHAIN_MIX)
    return jax.random.wrap_key_data(
        jax.random.key_data(base) ^ mix, impl=jax.random.key_impl(base)
    )
