"""Seeded Ouro weights made ON the device, in the dtype they are served in
(bfloat16; the gate float32), in one jitted call, every stacked leaf drawn a
slab at a time (`lfm2_weights._normal`).

Value policy, leaf by leaf of the program's `init_params` schema. The aim of
each choice is that a WRONG PATH SHOWS: every control of `families/ouro.py`
has to move the logits by more than the program's own bfloat16 rounding
does, through four passes of the stack.

- matrices: normal times `fan_in**-0.5` (`wq` and `wk` are kept outputs
  first, so their fan-in is their last axis; the head's its first). The
  matrices that write to the residual stream (`wo`, `w2`) take no smaller
  scale, as `lfm2`'s do: here an RMSNorm stands between each sublayer and
  the stream (`ln1_post`, `ln2_post`), which undoes any scale but the eps's
  share, and a scale of `hidden**-0.5 (2 layers)**-0.5` would put the
  sublayer's mean square (5e-6) beside `rms_norm_eps` (1e-6);
- the embedding: normal of unit variance. Loop 0 takes the embedding in
  where every later loop takes in a normed state of RMS about 1, so it
  stands at that size and not at `hidden**-0.5`;
- `ln1_post`, `ln2_post`: `POST_GAIN (1 + 0.3 normal)` with `POST_GAIN = (2
  layers)**-0.5`: what a sublayer adds has the RMS of its gain, so one loop's
  `2 layers` sublayers add about as much as the loop took in: the state
  neither blows up nor is drowned, in any of the four loops, whatever the
  depth (0.10 at 48 layers, 0.41 at 3). Gains away from a constant: a norm
  that skips its weight shows. `no_post_norms` adds the sublayers' outputs at
  an RMS near 1, ten times their share;
- `norm` (applied after every loop, carried): `1 + 0.3 normal`, as above:
  `no_loop_norm` carries a state that lacks these gains and is larger by
  `sqrt 2` a loop;
- `ln1`, `ln2`: ones;
- `wq`: times Q_GAIN (2): scores of standard deviation about 2, so a head
  attends to some keys among hundreds more than to others and does not
  average V: `shared_cache` (another loop's K and V) and a rounded K then
  move what the head picks. Not `kanana`'s 3: through 192 layer passes a
  sharper softmax amplifies every rounding (my chip runs, PR 46, one seed:
  the sound program reads 0.029 / 0.050 / 0.135 of the logits at gains of 1
  / 2 / 3, int8 pages 0.041 / 0.045 / 0.021 of `cache_excess`), and at 3 the
  bfloat16 program's own rounding stood at a third of `loops_3`'s reading.
  Larger output-norm gains do the same (2 and 3 times `POST_GAIN`: 0.33 and
  0.46 at a gain of 3), smaller ones leave the stream's own rounding larger
  than int8 matmul inputs';
- the gate `gate_w` [hidden] float32: normal times `GATE_STD hidden**-0.5`
  with GATE_STD 0.4, `gate_b` zero: `g_t` has a standard deviation of 0.4
  times the state's RMS (1 to 1.05), `lambda_t = sigmoid(g_t)` lies in 0.2-0.8
  out to 3.3 standard deviations and never saturates, so at the published
  threshold 1 every row reads the last loop, and at a threshold of 0.5 about
  half the rows read loop 0 and the rest loop 1 (the CPU test's case).

These arrays are the benchmark's inputs: the engine serves them and
`ouro_reference.py` reads the same arrays.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .lfm2_weights import _normal

Q_GAIN = 2.0
NORM_STD = 0.3
GATE_STD = 0.4
OUT_FIRST = ("wq", "wk")  # kept [layers, out, in] (models/ouro.py); the head and the rest inputs first


def build(schema: dict, seed_lo, seed_hi, n_layers: int):
    root = jax.random.fold_in(jax.random.key(seed_lo), seed_hi)
    post_gain = (2 * n_layers) ** -0.5

    def leaf(path, sds):
        name = str(path[-1].key)
        salt = sum((i + 1) * sum(ord(ch) for ch in str(getattr(p, "key", getattr(p, "idx", "")))) + 31 * i
                   for i, p in enumerate(path))
        key = jax.random.fold_in(root, salt)
        shape, dtype = sds.shape, sds.dtype
        if name in ("ln1", "ln2"):
            return jnp.ones(shape, dtype)
        if name in ("ln1_post", "ln2_post", "norm"):
            gain = 1.0 if name == "norm" else post_gain
            return (gain * (1.0 + NORM_STD * jax.random.normal(key, shape))).astype(dtype)
        if name == "gate_b":
            return jnp.zeros(shape, dtype)
        if name == "gate_w":
            return (GATE_STD * shape[0] ** -0.5 * jax.random.normal(key, shape)).astype(dtype)
        if name == "embed":
            return _normal(key, shape, 1.0, dtype)
        fan_in = shape[-1] if name in OUT_FIRST else shape[-2]
        return _normal(key, shape, fan_in ** -0.5 * (Q_GAIN if name == "wq" else 1.0), dtype)

    return jax.tree_util.tree_map_with_path(leaf, schema)


def make(program_config, mesh, seed: int):
    """Weights for `program_config` whole on every device of `mesh`."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from agentcontrolplane_tpu.models.ouro import init_params

    schema = jax.eval_shape(lambda: init_params(program_config, jax.random.key(0)))
    fn = lambda lo, hi: build(schema, lo, hi, program_config.n_layers)  # noqa: E731
    lo, hi = jnp.uint32(seed & 0x7FFFFFFF), jnp.uint32(seed >> 31)
    whole = jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), schema)
    return jax.jit(fn, out_shardings=whole)(lo, hi)
