"""What `test_dots.py` and `test_dots_operators.py` share: the tiny file's program, weights and sizes, a batch of
text tokens, and a prompt prefilled through the pool of three leaves."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from acpbench import spec
from acpbench.families import dots as family_module
from agentcontrolplane_tpu.models import dots
from agentcontrolplane_tpu.parallel.mesh import make_mesh

FILE = spec.load_json(spec.os.path.join(spec.ROOT, "tests/acpbench/data/tiny-config-dots.json"))
PAGE = FILE["engine"]["page_size"]
ONE_CHIP = lambda: make_mesh({"tp": 1}, devices=jax.devices()[:1])  # noqa: E731


@functools.lru_cache(maxsize=None)
def built(seed=5):
    family = spec.family(FILE)
    pc = dataclasses.replace(family.program_config(FILE), dtype=jnp.float32)
    return family, pc, ONE_CHIP(), family.weights(FILE, pc, ONE_CHIP(), seed)


def sizes():
    return family_module._sizes(FILE)


def text_tokens(B=2, T=40, seed=1):
    tokens = np.random.default_rng(seed).integers(0, 256, (B, T)).astype(np.int32)
    return tokens, np.tile(np.arange(T), (B, 1))


def lanes(B):
    return jnp.arange(B, dtype=jnp.int32), jnp.zeros((B,), jnp.int32)


def paged_setup(pc, B, M):
    cache = dots.init_paged_cache(pc, 1 + B * M, PAGE, max_slots=B)
    tables = (1 + jnp.arange(B * M, dtype=jnp.int32)).reshape(B, M)
    return cache, tables


def prefilled(pc, params, tokens, lengths, M=8, T=40):
    B = tokens.shape[0]
    cache, tables = paged_setup(pc, B, M)
    lengths = jnp.asarray(lengths, jnp.int32)
    ids = jnp.where(jnp.arange(T // PAGE)[None] < -(-lengths // PAGE)[:, None], tables[:, : T // PAGE], 0)
    prompt = jnp.where(jnp.arange(T)[None] < lengths[:, None], jnp.asarray(tokens)[:, :T], 0)
    cache, logits = dots.prefill_paged_batch(params, cache, prompt, lengths, ids, lanes(B), pc)
    return cache, tables, lengths, logits
