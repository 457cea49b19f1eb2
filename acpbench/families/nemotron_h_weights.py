"""Seeded Nemotron-H weights made ON the device, in the dtypes they are
served in, in one jitted call, every stacked leaf drawn a slab at a time
(`lfm2_weights._normal`).

Value policy, leaf by leaf of the program's `init_params` schema. The Mamba-2
leaves follow the published initialisation (Dao and Gu 2024, the reference
code's `Mamba2.__init__`, with the configuration's own `time_step_min`,
`time_step_max` and `time_step_floor`), so that the state matters over
hundreds of tokens as a trained model's does:

- `A_log` [H] float32: the log of a value drawn uniform in [1, 16] a head
  (`A_init_range`): `A = -(1..16)`, one scalar a head;
- `dt_bias` [H] float32: the inverse softplus of a step drawn log-uniform in
  [1e-3, 1e-1] a head and held over 1e-4, so a head forgets over 1 to 1,000
  tokens; `D` ones, as published;
- `conv_w` [taps, channels]: normal times `taps**-0.5`; `conv_b`: normal
  times CONV_BIAS_STD (0.25), large enough that a conv without its bias is
  seen; `gate_norm`: 1 + NORM_STD normal (a weight that is exactly 1 cannot
  show a norm that skips it);
- matrices: normal times `fan_in**-0.5` (the embedding by its width);
- the matrices that write to the residual stream (`out_proj`, `wo`, `up`,
  `sw2`): that, times the embedding's own scale `hidden**-0.5`, times
  `layers**-0.5` (one mixer a block: 11 sublayers), `lfm2_weights.py`'s scale
  and for its reason; `wo` times ATTN_OUT_GAIN (3) and `wq` times Q_GAIN (3),
  as `jamba_weights.py`: one attention layer of eleven, whose head attends to
  a few keys so that a wrong page moves the stream. `up` takes NO gain of its
  own: a chip that holds 64 of 512 experts computes an eighth of a layer's
  routed sum (about 2.75 held experts a token at a weight of 5 / 22 each), a
  third of what the shared expert adds, and that is enough for a skipped
  latent projection to read 0.25 of the logits. At a gain of 3 and of 2 every
  choice of a held expert that bfloat16 flipped showed threefold and twofold
  in the engine's own tokens (`greedy_regret` 0.89 and 0.27-0.55 beside a
  swapped page's 0.86-0.91: my chip runs, PR 54, calls 2 and 3);
- `router`: unit-variance logits; `router_bias` normal times BIAS_STD (0.01:
  among 512 sigmoid scores the 22nd and 23rd lie under 0.005 apart);
- `ln`, `norm`: ones (the head is not tied); `lm_head`: normal times
  `hidden**-0.5`.

These arrays are the benchmark's inputs: the engine serves them and
`nemotron_h_reference.py` reads the same arrays.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .lfm2_weights import _normal

NORM_STD = 0.1
CONV_BIAS_STD = 0.25
BIAS_STD = 0.01
Q_GAIN = 3.0
A_RANGE = (1.0, 16.0)
RESIDUAL_OUT = {"out_proj": 1.0, "wo": 3.0, "up": 1.0, "sw2": 1.0}  # what writes to the residual stream, and its gain


def build(schema: dict, seed_lo, seed_hi, hidden: int, n_layers: int, steps: tuple[float, float, float]):
    root = jax.random.fold_in(jax.random.key(seed_lo), seed_hi)
    dt_min, dt_max, dt_floor = steps

    def leaf(path, sds):
        name = str(path[-1].key)
        salt = sum((i + 1) * sum(ord(ch) for ch in str(getattr(p, "key", getattr(p, "idx", "")))) + 31 * i
                   for i, p in enumerate(path))
        key = jax.random.fold_in(root, salt)
        shape, dtype = sds.shape, sds.dtype
        if name in ("ln", "norm", "D"):
            return jnp.ones(shape, dtype)
        if name == "gate_norm":
            return (1.0 + NORM_STD * jax.random.normal(key, shape)).astype(dtype)
        if name == "A_log":
            return jnp.log(jax.random.uniform(key, shape, minval=A_RANGE[0], maxval=A_RANGE[1])).astype(dtype)
        if name == "dt_bias":
            step = jnp.exp(jax.random.uniform(key, shape, minval=math.log(dt_min), maxval=math.log(dt_max)))
            step = jnp.maximum(step, dt_floor)
            return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)
        if name == "conv_b":
            return (CONV_BIAS_STD * jax.random.normal(key, shape)).astype(dtype)
        if name == "router_bias":
            return (BIAS_STD * jax.random.normal(key, shape)).astype(jnp.float32)
        if name == "conv_w":
            return _normal(key, shape, shape[-2] ** -0.5, dtype)
        fan_in = shape[-1] if name == "embed" else shape[-2]
        scale = fan_in ** -0.5
        if name in RESIDUAL_OUT:
            scale *= hidden ** -0.5 * n_layers ** -0.5 * RESIDUAL_OUT[name]
        if name == "wq":
            scale *= Q_GAIN
        return _normal(key, shape, scale, dtype)

    return jax.tree_util.tree_map_with_path(leaf, schema)


def make(program_config, mesh, seed: int, steps: tuple[float, float, float] = (1e-3, 1e-1, 1e-4)):
    """Weights for `program_config` whole on every device of `mesh`."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from agentcontrolplane_tpu.models.nemotron_h import init_params

    schema = jax.eval_shape(lambda: init_params(program_config, jax.random.key(0)))
    fn = lambda lo, hi: build(schema, lo, hi, program_config.dim, program_config.n_layers, steps)  # noqa: E731
    lo, hi = jnp.uint32(seed & 0x7FFFFFFF), jnp.uint32(seed >> 31)
    whole = jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), schema)
    return jax.jit(fn, out_shardings=whole)(lo, hi)
