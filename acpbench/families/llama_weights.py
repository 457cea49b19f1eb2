"""Seeded weights made ON the device, in the types they are served in.

Same pytree as the program's `random_quantized_init` (which builds them on
the host, ~39 s of a ~100 s set-up): int8 values uniform over [-127, 127]
with one f32 scale per output channel that gives a matrix the variance of
a `fan_in**-0.5` normal; bf16 normal embedding and head; unit norms; q, k
and v biases normal with BIAS_STD (the program's init leaves them zero, and
a bias that is zero cannot show a bias path that is broken). One jitted call, every leaf born with its sharding, each stacked
matrix drawn layer by layer inside the call so that the generator's
temporaries stay one layer wide.

These arrays are the benchmark's inputs: the engine serves them and
`llama_reference.py` reads the same arrays. Neither sees anything the other made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

UNIFORM_INT8_STD = 73.61  # std of the integers -127..127
QUANTIZED = ("wq", "wk", "wv", "wo", "w1", "w2", "w3")
ROW_CHUNK = 128  # rows of the embedding / head drawn at a time
BIAS_STD = 0.1  # beside projections of unit variance: dropping a bias moves every logit, and the logits keep their size


def _int8_stack(key, shape):
    """[L, in, out] int8, one layer per step of a device loop."""
    L = shape[0]

    def one(l):
        bits = jax.random.bits(jax.random.fold_in(key, l), shape[1:], jnp.uint8)
        return jnp.maximum(jax.lax.bitcast_convert_type(bits, jnp.int8), -127)

    return jax.lax.map(one, jnp.arange(L))


def _normal_rows(key, shape, scale, dtype):
    """[rows, cols] normal * scale in `dtype`, ROW_CHUNK rows at a time."""
    rows, cols = shape
    n = -(-rows // ROW_CHUNK)

    def one(i):
        return (jax.random.normal(jax.random.fold_in(key, i), (ROW_CHUNK, cols)) * scale).astype(dtype)

    return jax.lax.map(one, jnp.arange(n)).reshape(n * ROW_CHUNK, cols)[:rows]


def build(schema: dict, seed_lo, seed_hi, dtype, quantized_cls):
    """The value policy, leaf by leaf of `schema` (a pytree of
    ShapeDtypeStructs shaped like the program's `init_params`). The seed
    arrives as two traced 31-bit halves: a seed baked into the program
    would compile a new program for every seed."""
    root = jax.random.fold_in(jax.random.key(seed_lo), seed_hi)

    def leaf(path, sds):
        name = str(path[-1].key)
        in_layers = len(path) >= 2 and str(path[-2].key) == "layers"
        key = jax.random.fold_in(root, sum(ord(ch) * (i + 1) for i, ch in enumerate(name)))
        shape = sds.shape
        if name.startswith("ln") or name == "norm":
            return jnp.ones(shape, dtype)
        if name.startswith("b"):
            return (jax.random.normal(key, shape) * BIAS_STD).astype(dtype)
        if in_layers and name in QUANTIZED:
            fan_in = shape[-2]
            scale = jnp.full(shape[:-2] + (1, shape[-1]), fan_in**-0.5 / UNIFORM_INT8_STD, jnp.float32)
            return quantized_cls(q=_int8_stack(key, shape), scale=scale)
        fan_in = shape[-1] if name == "embed" else shape[-2]
        return _normal_rows(key, shape, fan_in**-0.5, dtype)

    return jax.tree_util.tree_map_with_path(leaf, schema)


def quantized_shardings(mesh, base: dict, schema_q: dict, quantized_cls):
    """NamedShardings for the int8 pytree: the values take the matrix's
    spec; the [.., 1, out] scales keep only the output axis."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def expand(sharding, leaf):
        if isinstance(leaf, quantized_cls):
            spec = tuple(sharding.spec) + (None,) * (leaf.q.ndim - len(sharding.spec))
            return quantized_cls(q=sharding, scale=NamedSharding(mesh, P(*spec[:-2], None, spec[-1])))
        return sharding

    return jax.tree_util.tree_map(expand, base, schema_q, is_leaf=lambda x: isinstance(x, NamedSharding))


def make(llama_config, mesh, seed: int):
    """Weights for `llama_config` on `mesh`, from `seed`, in one jitted call."""
    from agentcontrolplane_tpu.models.llama import init_params
    from agentcontrolplane_tpu.ops.quant import QuantizedTensor
    from agentcontrolplane_tpu.parallel.mesh import param_shardings

    schema = jax.eval_shape(lambda: init_params(llama_config, jax.random.key(0)))
    fn = lambda lo, hi: build(schema, lo, hi, llama_config.dtype, QuantizedTensor)  # noqa: E731
    lo, hi = jnp.uint32(seed & 0x7FFFFFFF), jnp.uint32(seed >> 31)
    schema_q = jax.eval_shape(fn, lo, hi)
    shardings = quantized_shardings(
        mesh, param_shardings(mesh, llama_config, schema), schema_q, QuantizedTensor
    )
    return jax.jit(fn, out_shardings=shardings)(lo, hi)
