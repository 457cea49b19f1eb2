"""Routed experts as a layer's FF, and their counters' description: the one
call of ``ops.moe.routed_experts`` every expert family makes (``lfm2``,
``mellum``, ``kanana``, ``exaone``, ``keye``, ``dots``; ``nemotron_h``'s latent
experts call it themselves and describe their counters here). A mechanism
module (``docs/serving-engine.md``, "Adding a family"): it imports ``ops/`` and
``models/stack.py`` alone, and an edit here is an edit to those cells.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.moe import COUNTS_HEAD, routed_experts
from .stack import mm

# tokens the routed FF takes at a time where a family asks for chunks: a long
# prefill's rows go through the grouped matmul a chunk at a time, so that its
# sorted copies of the rows (tokens x k of them, in and out) stay a chunk wide:
# at 8,192 tokens they were 1.7 GB of a ``mellum`` prefill's 2.6 GB of
# temporaries beside 12.9 GB resident
MOE_CHUNK = 2048


def routed_ff(x, ff, stacks, layer_index, c, valid, chosen=None, *, score="softmax", bias=False, scale=1.0,
              chunk=False, shared=False):
    """The FF of expert layer ``layer_index`` (traced) over ``x`` [B, T, D]
    normed: ``ff`` holds its router, ``stacks`` every expert layer's experts
    flattened to one leading axis, which the grouped matmul indexes from
    ``layer_index * held`` (a slice of a stack handed to an opaque kernel would
    be copied out first, every step). ``chosen`` [B, T, k] is a routing given
    and not made (``route`` of the programs). What the families differ in, each
    read off its config by the caller: ``score`` the router's (``softmax`` |
    ``sigmoid``), ``bias`` whether ``ff["router_bias"]`` steers the selection,
    ``scale`` the routed sum's factor, ``chunk`` whether rows past ``MOE_CHUNK``
    go a chunk at a time, ``shared`` whether a shared expert (``sw1``, ``sw3``,
    ``sw2``) is added over every row. -> (FF output [B, T, D], counters)."""
    B, T, D = x.shape
    k = c.experts_per_token

    def routed(rows):
        x, valid, chosen = rows
        return routed_experts(x, ff["router"], *stacks, k, held=c.held, score=score,
                              bias=ff["router_bias"] if bias else None, renormalize=c.norm_topk_prob, scale=scale,
                              valid=valid, expert_base=layer_index * len(c.held), chosen=chosen)

    rows = (x.reshape(B * T, D), valid.reshape(B * T), None if chosen is None else chosen.reshape(B * T, k))
    if chunk and B * T > MOE_CHUNK and B * T % MOE_CHUNK == 0:
        chunked = jax.tree_util.tree_map(lambda a: a.reshape((-1, MOE_CHUNK) + a.shape[1:]), rows)
        y, counts = jax.lax.map(routed, chunked)
        counts = jnp.sum(counts, axis=0, dtype=jnp.uint32)
    else:
        y, counts = routed(rows)
    if shared:
        with jax.named_scope("moe_shared"):
            y = y.reshape(B, T, D) + mm(jax.nn.silu(mm(x, ff["sw1"])) * mm(x, ff["sw3"]), ff["sw2"])
    else:
        y = y.reshape(B, T, D)
    return y, jnp.concatenate([jnp.ones((1,), jnp.uint32), counts])


def describe_moe(config, total) -> dict:
    """``Engine.stats()["moe"]`` from the counters summed by the engine
    (``total`` [2, 1 + COUNTS_HEAD + held], None before the first dispatch):
    decode steps and prefills apart, expert layers run, (token, choice)
    pairs routed (padding lanes route nowhere and are not counted), pairs
    that landed on held experts, held experts read (an expert with a pair
    in a layer), and the pairs each held expert took."""
    held = len(config.held)
    if total is None:
        total = [[0] * (1 + COUNTS_HEAD + held)] * 2

    def row(r):
        return {"expert_layers": int(r[0]), "pairs_routed": int(r[1]), "pairs_held": int(r[2]),
                "experts_read": int(r[3]), "tokens_per_held_expert": [int(n) for n in r[4:]]}

    return {"moe": {"experts": config.n_experts, "held": held, "experts_per_token": config.experts_per_token,
                    "decode": row(total[0]), "prefill": row(total[1])}}
