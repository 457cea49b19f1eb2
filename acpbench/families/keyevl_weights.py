"""Seeded Keye-VL-2.0 weights made ON the device, in the dtype they are
served in (bfloat16), in one jitted call, every stacked leaf drawn a slab at
a time (`lfm2_weights._normal`).

Value policy, leaf by leaf of the program's `init_params` schema; where it
is `mellum_weights.py`'s (the same GQA with q/k norms and the same softmax
router) the reason is given there. The aim of each choice of this file is
that a WRONG CHOICE OF ROWS SHOWS, and that rounding does not make it:

- matrices: normal times `fan_in**-0.5` (the embedding by its width);
- `wo`, `w2` (what writes to the residual stream): that, times `hidden**-0.5
  (2 layers)**-0.5`; `wo` times ATTN_OUT_GAIN (3) besides: attention carries
  about half of what the sublayers add, and more on a chip that holds an
  eighth of the experts, so attention over other rows than the indexer's (the
  most recent, half as many, all of them) moves the logits by far more than
  the stream's own rounding;
- `q_norm`: Q_NORM_GAIN (3) times (1 + 0.1 normal), `k_norm`: 1 + 0.1 normal:
  scores of standard deviation about 3, so a head attends to a few keys of
  its 2,048 and does not average V: WHICH rows are among them matters;
- `iq`, `ik`, `iw` (the indexer's three projections): plain `fan_in**-0.5`.
  `qI` has unit entries, `kI` unit entries after its norm, a head's product
  over 64 values a standard deviation of 8, and `w` unit signed values: an
  index score `sum_j w_j relu(qI_j . kI_s)` spreads over about +-22 a query.
  Rounding `qI` and `kI` to bfloat16 moves a score by about 0.1, so of the
  2,048 rows at the threshold (one score in eight to thirteen) rounding
  decides a few of a hundred and the rest are chosen by a margin; `w` signed
  (as a trained head's gate may be) makes `w = 1` another choice altogether.
  The turned pairs of `qI . kI` carry half of the product at 12k-26k
  positions of distance, so the indexer's rope left off is another choice;
- `ik_norm`: 1 + IK_NORM_STD (0.3) normal, `ik_bias`: IK_NORM_STD normal:
  gains away from 1 and a bias away from 0, so a norm that is skipped, or
  that skips its weight or its bias, orders the rows otherwise;
- `ln1`, `ln2`, `norm`: ones; `router`: normal times `hidden**-0.5`
  (`mellum_weights.py`: a softmax over 128 whose eighth and ninth largest lie
  close; the check compares logits with the program's choice of experts
  given).

These arrays are the benchmark's inputs: the engine serves them and
`keyevl_reference.py` reads the same arrays.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .lfm2_weights import _normal

QK_NORM_STD = 0.1
Q_NORM_GAIN = 3.0
IK_NORM_STD = 0.3
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
        if name in ("ik_norm", "ik_bias"):
            return ((name == "ik_norm") + IK_NORM_STD * jax.random.normal(key, shape)).astype(dtype)
        fan_in = shape[-1] if name == "embed" else shape[-2]
        scale = fan_in ** -0.5
        if name in RESIDUAL_OUT:
            scale *= hidden ** -0.5 * (2 * n_layers) ** -0.5 * (ATTN_OUT_GAIN if name == "wo" else 1.0)
        return _normal(key, shape, scale, dtype)

    return jax.tree_util.tree_map_with_path(leaf, schema)


def make(program_config, mesh, seed: int):
    """Weights for `program_config` whole on every device of `mesh`."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from agentcontrolplane_tpu.models.keye import init_params

    schema = jax.eval_shape(lambda: init_params(program_config, jax.random.key(0)))
    fn = lambda lo, hi: build(schema, lo, hi, program_config.dim, program_config.n_layers)  # noqa: E731
    lo, hi = jnp.uint32(seed & 0x7FFFFFFF), jnp.uint32(seed >> 31)
    whole = jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), schema)
    return jax.jit(fn, out_shardings=whole)(lo, hi)
