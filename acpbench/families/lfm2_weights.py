"""Seeded LFM2-MoE weights made ON the device, in the dtype they are served
in (bfloat16), in one jitted call, every stacked leaf drawn a slab at a time
so that the generator's float32 temporaries stay one slab wide.

Value policy, leaf by leaf of the program's `init_params` schema:

- matrices: normal times `fan_in**-0.5` (the embedding, tied to the head,
  by its width, so that logits have about unit variance);
- the matrices that write to the residual stream (`conv_out`, `wo`, every
  `w2`): that, times the embedding's own scale `hidden**-0.5`, times
  `(2 * layers)**-0.5` (the scaled initialisation of residual projections
  that GPT-2 and Megatron-LM publish): each of the 80 sublayers adds
  1/80 of the embedding's energy and the stream stays within twice its
  first size. With every sublayer at unit scale instead, the stream is
  made of sublayer outputs alone, each m-th of them 1/sqrt(m) of what it
  meets, and bfloat16's rounding grew fiftyfold through the gates and the
  routers' choices on its way down (the reference with its matmul inputs
  rounded to bfloat16 read 0.16 from itself in float32: my chip run,
  PR 31): int8 pages could no longer be told from bfloat16 ones;
- `conv_w` [D, taps]: normal times `taps**-0.5`: the convolution of `s`
  keeps its variance;
- `wo`, the attention layers' output projection: that, times ATTN_OUT_GAIN
  (3): the ten attention layers of forty then carry about half of what the
  sublayers add. At the conv layers' scale they carried an eighth, and the
  pool's precision could not be seen: int8 pages moved the logits by a
  fifth of what the program's own bfloat16 rounding does (`cache_excess`
  +0.03 against a sound spread of 0.015; PERF.md, PR 31);
- `ln1`, `ln2`: ones; `k_norm`: 1 + QK_NORM_STD normal, near 1, and a
  weight that is exactly 1 cannot show a norm that skips it; `q_norm`:
  Q_NORM_GAIN (3) times such a weight. With both at 1 the attention logits
  have unit variance and the softmax over a hundred keys is near flat: every
  head averages V, and neither a wrong page nor a rounded one moves it.
  Trained QK-norm gains are several; at 3 a head attends to a few keys;
- `norm`, the last RMSNorm's weight: +1 or -1 by a coin a channel. The head
  is tied to the embedding and half the stream's energy is the current
  token's own embedding row, so with a weight of ones every position's
  largest logit is its own token, 30 standard deviations clear: greedy
  decoding would emit it whatever the pages hold, and the check's
  `greedy_regret` would read 0 of a broken page table. Signs leave every
  logit its variance and take the row's product with itself to zero;
- `router_bias` [E] float32: normal times BIAS_STD. The scores are
  sigmoids of about unit-variance logits (std 0.21, the top few of 64 about
  0.02 apart); a bias of std 0.02 changes which four are chosen for a
  large share of tokens (measured in tests/engine/test_lfm2.py) and leaves
  the experts' loads near even, as a trained balancing bias does, so a
  router that drops the bias, or lets it into the weights, is seen by the
  check and the experts a chip reads a step do not hang on the seed.

These arrays are the benchmark's inputs: the engine serves them and
`lfm2_reference.py` reads the same arrays.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BIAS_STD = 0.02
QK_NORM_STD = 0.1
Q_NORM_GAIN = 3.0
ATTN_OUT_GAIN = 3.0
SLAB = 1 << 24  # elements drawn at a time
RESIDUAL_OUT = ("conv_out", "wo", "w2")  # what writes to the residual stream


def _normal(key, shape, scale, dtype):
    """normal * scale in `dtype`, the leading axes a slab at a time."""
    size = 1
    for n in shape:
        size *= n
    if size <= SLAB or len(shape) < 2:
        return (jax.random.normal(key, shape) * scale).astype(dtype)
    lead = shape[0]
    if size // lead > SLAB and len(shape) == 2:  # a tall matrix: rows in chunks
        rows = max(1, SLAB // shape[1])
        n = -(-lead // rows)
        out = jax.lax.map(lambda i: _normal(jax.random.fold_in(key, i), (rows, shape[1]), scale, dtype),
                          jnp.arange(n))
        return out.reshape(n * rows, shape[1])[:lead]
    return jax.lax.map(lambda i: _normal(jax.random.fold_in(key, i), shape[1:], scale, dtype),
                       jnp.arange(lead))


def build(schema: dict, seed_lo, seed_hi, dtype, hidden: int, n_layers: int):
    root = jax.random.fold_in(jax.random.key(seed_lo), seed_hi)

    def leaf(path, sds):
        name = str(path[-1].key)
        salt = sum((i + 1) * sum(ord(ch) for ch in str(getattr(p, "key", getattr(p, "idx", "")))) + 31 * i
                   for i, p in enumerate(path))
        key = jax.random.fold_in(root, salt)
        shape = sds.shape
        if name in ("ln1", "ln2"):
            return jnp.ones(shape, dtype)
        if name == "norm":
            return jnp.where(jax.random.bernoulli(key, 0.5, shape), 1.0, -1.0).astype(dtype)
        if name in ("q_norm", "k_norm"):
            gain = Q_NORM_GAIN if name == "q_norm" else 1.0
            return (gain * (1.0 + QK_NORM_STD * jax.random.normal(key, shape))).astype(dtype)
        if name == "router_bias":
            return (BIAS_STD * jax.random.normal(key, shape)).astype(jnp.float32)
        if name == "conv_w":
            return _normal(key, shape, shape[-1] ** -0.5, dtype)
        fan_in = shape[-1] if name == "embed" else shape[-2]
        scale = fan_in ** -0.5
        if name in RESIDUAL_OUT:
            scale *= hidden ** -0.5 * (2 * n_layers) ** -0.5 * (ATTN_OUT_GAIN if name == "wo" else 1.0)
        return _normal(key, shape, scale, dtype)

    return jax.tree_util.tree_map_with_path(leaf, schema)


def make(program_config, mesh, seed: int):
    """Weights for `program_config` whole on every device of `mesh`."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from agentcontrolplane_tpu.models.lfm2 import init_params

    schema = jax.eval_shape(lambda: init_params(program_config, jax.random.key(0)))
    fn = lambda lo, hi: build(schema, lo, hi, program_config.dtype, program_config.dim,  # noqa: E731
                              program_config.n_layers)
    lo, hi = jnp.uint32(seed & 0x7FFFFFFF), jnp.uint32(seed >> 31)
    whole = jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), schema)
    return jax.jit(fn, out_shardings=whole)(lo, hi)
