"""Seeded Jamba weights made ON the device, in the dtypes they are served in,
in one jitted call, every stacked leaf drawn a slab at a time
(`lfm2_weights._normal`).

Value policy, leaf by leaf of the program's `init_params` schema. The Mamba
leaves follow the published Mamba initialisation (Gu and Dao 2023, section
3.6 and the reference code's `Mamba.__init__`), so that the state matters
over hundreds of tokens as a trained model's does:

- `A_log` [d_state, d_inner] float32: `log(1..16)` down every channel (the
  S4D-real initialisation): `A = -(1..16)`, so with the steps below a
  channel's slowest state forgets over 10 to 1,000 tokens and its fastest
  over 1 to 60;
- `dt_bias` [d_inner] float32: the inverse softplus of a step drawn
  log-uniform in [1e-3, 1e-1] a channel, so that `delta` starts in that
  range whatever `dt_proj` adds;
- `dt_proj` [dt_rank, d_inner]: normal times `dt_rank**-0.5` (the code's
  "random" choice at `dt_scale` 1); `dt_norm`, `b_norm`, `c_norm`: 1 +
  NORM_STD normal: near 1, and a weight that is exactly 1 cannot show a norm
  that skips it. What the norms divide by is not 1: `u'` is a silu of a
  unit-variance conv, about 0.36 in mean square, so leaving the norms out
  shrinks `dt`, `B` and `C` by 0.6 each (the `nonorm` control);
- `D` [d_inner] float32: ones, as published;
- `conv_w` [taps, d_inner]: normal times `taps**-0.5`: the convolution of `u`
  keeps its variance; `conv_b`: normal times CONV_BIAS_STD (0.25, about the
  width of PyTorch's uniform(-0.5, 0.5) for four taps), large enough that a
  conv without its bias is seen;
- matrices: normal times `fan_in**-0.5` (the embedding, tied to the head,
  by its width, so that logits have about unit variance);
- the matrices that write to the residual stream (`out_proj`, `wo`, every
  `w2`): that, times the embedding's own scale `hidden**-0.5`, times
  `(2 * layers)**-0.5`, the scale `lfm2_weights.py` uses and for its reason:
  each of the 56 sublayers adds 1/56 of the embedding's energy and the
  stream stays within twice its first size, so that bfloat16's rounding is
  not grown by a stream made of sublayer outputs alone;
- `wo`: that, times ATTN_OUT_GAIN (3), and `wq` times Q_GAIN (3): two
  attention layers of 28 with logits of unit variance would average V over a
  hundred keys and add a fiftieth of what the sublayers add; at these gains
  a head attends to a few keys and a wrong page moves the stream;
- `ln1`, `ln2`: ones; `norm`, the last RMSNorm's weight: +1 or -1 by a coin
  a channel, for `lfm2_weights.py`'s reason (a tied head would else rank
  every position's own token first, 30 deviations clear, whatever the pages
  and the state hold).

These arrays are the benchmark's inputs: the engine serves them and
`jamba_reference.py` reads the same arrays.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .lfm2_weights import _normal

NORM_STD = 0.1
CONV_BIAS_STD = 0.25
Q_GAIN = 3.0
ATTN_OUT_GAIN = 3.0
DT_MIN, DT_MAX = 1e-3, 1e-1
RESIDUAL_OUT = ("out_proj", "wo", "w2")


def build(schema: dict, seed_lo, seed_hi, hidden: int, n_layers: int):
    root = jax.random.fold_in(jax.random.key(seed_lo), seed_hi)

    def leaf(path, sds):
        name = str(path[-1].key)
        salt = sum((i + 1) * sum(ord(ch) for ch in str(getattr(p, "key", getattr(p, "idx", "")))) + 31 * i
                   for i, p in enumerate(path))
        key = jax.random.fold_in(root, salt)
        shape, dtype = sds.shape, sds.dtype
        if name in ("ln1", "ln2"):
            return jnp.ones(shape, dtype)
        if name == "norm":
            return jnp.where(jax.random.bernoulli(key, 0.5, shape), 1.0, -1.0).astype(dtype)
        if name in ("dt_norm", "b_norm", "c_norm"):
            return (1.0 + NORM_STD * jax.random.normal(key, shape)).astype(dtype)
        if name == "A_log":  # [M, d_state, d_inner]
            return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[-2] + 1, dtype=jnp.float32))[:, None], shape).astype(dtype)
        if name == "D":
            return jnp.ones(shape, dtype)
        if name == "dt_bias":
            step = jnp.exp(jax.random.uniform(key, shape, minval=math.log(DT_MIN), maxval=math.log(DT_MAX)))
            return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)
        if name == "conv_b":
            return (CONV_BIAS_STD * jax.random.normal(key, shape)).astype(dtype)
        if name == "conv_w":
            return _normal(key, shape, shape[-2] ** -0.5, dtype)
        fan_in = shape[-1] if name == "embed" else shape[-2]
        scale = fan_in ** -0.5
        if name in RESIDUAL_OUT:
            scale *= hidden ** -0.5 * (2 * n_layers) ** -0.5 * (ATTN_OUT_GAIN if name == "wo" else 1.0)
        if name == "wq":
            scale *= Q_GAIN
        return _normal(key, shape, scale, dtype)

    return jax.tree_util.tree_map_with_path(leaf, schema)


def make(program_config, mesh, seed: int):
    """Weights for `program_config` whole on every device of `mesh`."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from agentcontrolplane_tpu.models.jamba import init_params

    schema = jax.eval_shape(lambda: init_params(program_config, jax.random.key(0)))
    fn = lambda lo, hi: build(schema, lo, hi, program_config.dim, program_config.n_layers)  # noqa: E731
    lo, hi = jnp.uint32(seed & 0x7FFFFFFF), jnp.uint32(seed >> 31)
    whole = jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), schema)
    return jax.jit(fn, out_shardings=whole)(lo, hi)
