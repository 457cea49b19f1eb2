"""Seeded K-EXAONE weights made ON the device, in the dtype they are served
in (bfloat16), in one jitted call, every stacked leaf drawn a slab at a time
(`lfm2_weights._normal`).

Value policy, leaf by leaf of the program's `init_params` schema: `mellum`'s
for the attention and `kanana`'s for the expert layer, so that a WRONG PATH
SHOWS (their files give each reason), plus what this family alone has:

- matrices: normal times `fan_in**-0.5` (the embedding by its width); those
  that write to the residual stream (`wo`, every `w2`, `sw2`): that, times
  `hidden**-0.5 (2 layers)**-0.5`; `wo` times ATTN_OUT_GAIN (3);
- `q_norm`: Q_NORM_GAIN (3) times `1 + 0.1 normal`, `k_norm`: `1 + 0.1
  normal`: a head attends to a few keys, so a wrong window, a rotated full
  layer or a refused row left counted moves the logits;
- `router`: unit-variance logits; `router_bias` normal times BIAS_STD (0.03);
  the routed experts' `w2` times EXPERT_OUT_GAIN (2): a chip that holds 16
  of 128 experts computes an eighth of a layer's routed sum, about one held
  expert a token at a weight near 2.5 / 8;
- the MTP module: `hnorm`, `enorm`, `norm` ones; `eh_proj` normal times
  `(2 hidden)**-0.5 hidden**-0.5`, so that `u` has the residual stream's own
  scale (the embedding's rows have RMS `hidden**-0.5`) and the block's
  attention and FF add to it what a layer of the stack adds; its block as a
  dense layer of the stack;
- `lm_head`: normal times `hidden**-0.5` times HEAD_GAIN. **The head gain is
  what sets the drafter's acceptance with seeded weights.** The drafted and
  the verified logits of random weights are independent, and speculative
  sampling keeps a draft with probability `sum_x min(p(x), q(x))`, which
  hangs on how peaked both are: logits of standard deviation `s` at
  temperature `T` over 19,200 tokens overlap 0.72 at `s / T = 0.5`, 0.70 at
  0.55, 0.67 at 0.6 (reckoned, independent normal logits). 0.385 at the
  mix's temperature 0.7 is `s / T = 0.55`. The configuration's file records
  the gain and the chip's reading under `assumed.acceptance`.

These arrays are the benchmark's inputs: the engine serves them and
`exaone_reference.py` reads the same arrays.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .lfm2_weights import _normal

BIAS_STD = 0.03
QK_NORM_STD = 0.1
Q_NORM_GAIN = 3.0
ATTN_OUT_GAIN = 3.0
EXPERT_OUT_GAIN = 2.0
HEAD_GAIN = 0.385
RESIDUAL_OUT = {"wo": ATTN_OUT_GAIN, "w2": 1.0, "sw2": 1.0}  # what writes to the residual stream, and its gain


def build(schema: dict, seed_lo, seed_hi, hidden: int, n_layers: int):
    root = jax.random.fold_in(jax.random.key(seed_lo), seed_hi)

    def leaf(path, sds):
        name = str(path[-1].key)
        salt = sum((i + 1) * sum(ord(ch) for ch in str(getattr(p, "key", getattr(p, "idx", "")))) + 31 * i
                   for i, p in enumerate(path))
        key = jax.random.fold_in(root, salt)
        shape, dtype = sds.shape, sds.dtype
        if name in ("ln1", "ln2", "norm", "hnorm", "enorm"):
            return jnp.ones(shape, dtype)
        if name in ("q_norm", "k_norm"):
            gain = Q_NORM_GAIN if name == "q_norm" else 1.0
            return (gain * (1.0 + QK_NORM_STD * jax.random.normal(key, shape))).astype(dtype)
        if name == "router_bias":
            return (BIAS_STD * jax.random.normal(key, shape)).astype(jnp.float32)
        fan_in = shape[-1] if name == "embed" else shape[-2]
        scale = fan_in ** -0.5
        if name in RESIDUAL_OUT:
            routed = name == "w2" and len(shape) == 4  # [layers, held, F, D]: the routed experts'
            scale *= hidden ** -0.5 * (2 * n_layers) ** -0.5 * RESIDUAL_OUT[name] * (EXPERT_OUT_GAIN if routed else 1.0)
        scale *= {"lm_head": HEAD_GAIN, "eh_proj": hidden ** -0.5}.get(name, 1.0)
        return _normal(key, shape, scale, dtype)

    return jax.tree_util.tree_map_with_path(leaf, schema)


def make(program_config, mesh, seed: int):
    """Weights for `program_config` whole on every device of `mesh`."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from agentcontrolplane_tpu.models.exaone import init_params

    schema = jax.eval_shape(lambda: init_params(program_config, jax.random.key(0)))
    fn = lambda lo, hi: build(schema, lo, hi, program_config.dim, program_config.n_layers)  # noqa: E731
    lo, hi = jnp.uint32(seed & 0x7FFFFFFF), jnp.uint32(seed >> 31)
    whole = jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), schema)
    return jax.jit(fn, out_shardings=whole)(lo, hi)
