"""Seeded Kanana-2 weights made ON the device, in the dtype they are served
in (bfloat16), in one jitted call, every stacked leaf drawn a slab at a time
(`lfm2_weights._normal`).

Value policy, leaf by leaf of the program's `init_params` schema; where it
is `lfm2_weights.py`'s the reason is given there. The aim of each choice is
that a WRONG PATH SHOWS: every control of `families/kanana.py` has to move
the logits by more than the program's own bfloat16 rounding does.

- matrices: normal times `fan_in**-0.5` (the embedding by its width);
- the matrices that write to the residual stream (`wo`, `w2`, `sw2`): that,
  times `hidden**-0.5 (2 layers)**-0.5`, the scaled initialisation of
  residual projections;
- `wo`: that, times ATTN_OUT_GAIN (3), as `lfm2`'s and `mellum`'s: attention
  carries about half of what the sublayers add, so a wrong scale, a wrong
  rotation or a rounded row moves the logits by more than the stream's own
  rounding;
- `wq_nope`, `wq_pe` (the source's `q_proj`, outputs first): times Q_GAIN (3). The source has no q norm (`q_lora_rank` null), so
  the sharpness `lfm2` gives its `q_norm` weight goes into the projection:
  scores of standard deviation about 3, a head attends to a few keys among
  three thousand and does not average V. `scale_128` (scores over
  `sqrt(128)`) is then a temperature 22% off, and shows;
- `wkv_c` and `wk_pe` (the source's `kv_a_proj_with_mqa`): its 512 latent
  columns times LATENT_GAIN (2), its 64 rope columns plain. The raw latent then has an RMS of 2 and the norm brings it to its
  weight: a path that skips the norm (`kv_norm_off`) doubles K and V, where
  at a gain of 1 the raw latent's RMS is 1 already and the norm could be
  left out unseen. The rope columns plain: `k_pe` of unit entries carries
  `64 / (64 + 128 x 1.09)` = 31% of a score's variance beside `k_nope`, a
  real share, so `k_pe_unroped` and `rope_all` show;
- `kv_norm`: 1 + KV_NORM_STD (0.3) normal: gains away from 1, so a norm that
  skips its weight shows too;
- `ln1`, `ln2`, `norm`: ones. The head is not tied to the embedding, so a
  position's own token has no logit of its own to win by;
- `router`: normal times `hidden**-0.5`: unit-variance logits, sigmoid
  scores of which the sixth and the seventh largest of 128 lie about 0.01
  apart: the check teacher-forces the routing on the rows read twice;
- `router_bias` [128] float32: normal times BIAS_STD (0.03), wider than the
  gap at the threshold, so it changes which six are chosen in most rows and
  leaves the loads near even: `bias_off` shows;
- the routed experts' `w2`: times EXPERT_OUT_GAIN (2). A chip that holds 8
  of 128 experts computes a sixteenth of a layer's routed sum: 0.375 held
  experts a token at weights near 2.448 / 6. At the shared expert's scale
  that is a twentieth of what the shared expert adds and neither the bias
  nor the scaling factor could be seen. At 4 they were seen fourfold
  (`route_scale_off` 0.46, `bias_off` 0.46 of the logits) and so was every
  choice that bfloat16 flips: the reference with bfloat16 matmul inputs,
  which routes freely, read 0.18 where the program with its routing given
  read 0.067, and the engine's own freely routed tokens 0.74-0.86 of a
  standard deviation of regret beside a swapped page's 1.80 (my chip run,
  PR 44, call 3). At 2 both controls still stand clear of the limit and a
  flip costs half as much. The shared expert (`sw1`, `sw3`, `sw2`) keeps
  the plain scales.

These arrays are the benchmark's inputs: the engine serves them and
`kanana_reference.py` reads the same arrays.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .lfm2_weights import _normal

BIAS_STD = 0.03
Q_GAIN = 3.0
LATENT_GAIN = 2.0
KV_NORM_STD = 0.3
ATTN_OUT_GAIN = 3.0
EXPERT_OUT_GAIN = 2.0
RESIDUAL_OUT = {"wo": ATTN_OUT_GAIN, "w2": 1.0, "sw2": 1.0}  # what writes to the residual stream, and its gain


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
        if name == "kv_norm":
            return (1.0 + KV_NORM_STD * jax.random.normal(key, shape)).astype(dtype)
        if name == "router_bias":
            return (BIAS_STD * jax.random.normal(key, shape)).astype(jnp.float32)
        # inputs first but: the embedding by its width, the query projections outputs first, a head's W_UK [128, 512]
        fan_in = shape[-1] if name in ("embed", "wq_nope", "wq_pe", "wuk") else shape[-2]
        scale = fan_in ** -0.5
        if name in RESIDUAL_OUT:
            routed = name == "w2" and len(shape) == 4  # [layers, held, F, D]: the routed experts', not the dense layer's
            scale *= hidden ** -0.5 * (2 * n_layers) ** -0.5 * RESIDUAL_OUT[name] * (EXPERT_OUT_GAIN if routed else 1.0)
        scale *= {"wq_nope": Q_GAIN, "wq_pe": Q_GAIN, "wkv_c": LATENT_GAIN}.get(name, 1.0)
        return _normal(key, shape, scale, dtype)

    return jax.tree_util.tree_map_with_path(leaf, schema)


def make(program_config, mesh, seed: int):
    """Weights for `program_config` whole on every device of `mesh`."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from agentcontrolplane_tpu.models.kanana import init_params

    schema = jax.eval_shape(lambda: init_params(program_config, jax.random.key(0)))
    fn = lambda lo, hi: build(schema, lo, hi, program_config.dim, program_config.n_layers)  # noqa: E731
    lo, hi = jnp.uint32(seed & 0x7FFFFFFF), jnp.uint32(seed >> 31)
    whole = jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), schema)
    return jax.jit(fn, out_shardings=whole)(lo, hi)
