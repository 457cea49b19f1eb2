"""Seeded Mellum2 weights made ON the device, in the dtype they are served
in (bfloat16), in one jitted call, every stacked leaf drawn a slab at a time
(`lfm2_weights._normal`).

Value policy, leaf by leaf of the program's `init_params` schema; where it
is `lfm2_weights.py`'s the reason is given there:

- matrices: normal times `fan_in**-0.5` (the embedding by its width);
- the matrices that write to the residual stream (`wo`, every `w2`): that,
  times `hidden**-0.5 (2 layers)**-0.5`, the scaled initialisation of
  residual projections: each of the 56 sublayers adds 1/56 of the
  embedding's energy;
- `wo`: that, times ATTN_OUT_GAIN (3): attention then carries about half of
  what the sublayers add, so a wrong window, a wrong table of frequencies
  or a rounded page moves the logits by more than the stream's own
  rounding. The routed FF of a chip that holds 16 of 64 experts adds a
  quarter of a whole layer's, so attention's share is larger still;
- `ln1`, `ln2`, `norm`: ones. The head is not tied to the embedding, so a
  position's own token has no logit of its own to win by (the reason
  `lfm2`'s last norm is sign-valued);
- `k_norm`: 1 + QK_NORM_STD normal; `q_norm`: Q_NORM_GAIN (3) times such a
  weight: a head attends to a few keys, among a thousand in a window layer
  and among all in a full layer, instead of averaging V. YaRN's attention
  factor multiplies q and k of a full layer besides (1.63 on the scores);
- `router`: normal times `hidden**-0.5`: unit-variance logits, a softmax
  over 64 whose eighth and ninth largest lie close: the check
  teacher-forces the routing on the rows read twice, as `lfm2`'s does.

These arrays are the benchmark's inputs: the engine serves them and
`mellum_reference.py` reads the same arrays.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .lfm2_weights import _normal

QK_NORM_STD = 0.1
Q_NORM_GAIN = 3.0
ATTN_OUT_GAIN = 3.0
RESIDUAL_OUT = ("wo", "w2")


def build(schema: dict, seed_lo, seed_hi, hidden: int, n_layers: int):
    root = jax.random.fold_in(jax.random.key(seed_lo), seed_hi)

    def leaf(path, sds):
        name = str(path[-1].key)
        salt = sum((i + 1) * sum(ord(ch) for ch in str(getattr(p, "key", getattr(p, "idx", "")))) + 31 * i
                   for i, p in enumerate(path))
        key = jax.random.fold_in(root, salt)
        shape, dtype = sds.shape, sds.dtype
        if name in ("ln1", "ln2", "norm"):
            return jnp.ones(shape, dtype)
        if name in ("q_norm", "k_norm"):
            gain = Q_NORM_GAIN if name == "q_norm" else 1.0
            return (gain * (1.0 + QK_NORM_STD * jax.random.normal(key, shape))).astype(dtype)
        fan_in = shape[-1] if name == "embed" else shape[-2]
        scale = fan_in ** -0.5
        if name in RESIDUAL_OUT:
            scale *= hidden ** -0.5 * (2 * n_layers) ** -0.5 * (ATTN_OUT_GAIN if name == "wo" else 1.0)
        return _normal(key, shape, scale, dtype)

    return jax.tree_util.tree_map_with_path(leaf, schema)


def make(program_config, mesh, seed: int):
    """Weights for `program_config` whole on every device of `mesh`."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from agentcontrolplane_tpu.models.mellum import init_params

    schema = jax.eval_shape(lambda: init_params(program_config, jax.random.key(0)))
    fn = lambda lo, hi: build(schema, lo, hi, program_config.dim, program_config.n_layers)  # noqa: E731
    lo, hi = jnp.uint32(seed & 0x7FFFFFFF), jnp.uint32(seed >> 31)
    whole = jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), schema)
    return jax.jit(fn, out_shardings=whole)(lo, hi)
