"""Seeded dots3-note-prev weights made ON the device, in the dtype they are
served in (bfloat16), in one jitted call, every stacked leaf drawn a slab at
a time (`lfm2_weights._normal`).

Value policy, leaf by leaf of the program's `init_params` schema; where it
is `kanana_weights.py`'s (the same latent attention, sigmoid router, bias and
shared expert) or `keyevl_weights.py`'s (the same indexer) the reason is
given there. The aim of each choice is that a WRONG CHOICE OF ROWS, A DROPPED
GATE AND A DROPPED RESCALE SHOW, and that rounding does not make them:

- matrices: normal times `fan_in**-0.5` (the embedding by its width; the
  query projections `wq_nope` / `wq_pe` outputs first; a head's `wuk` [nope,
  kv_rank]);
- `embed`: its rows in PAIRS, row `2i + 1` the negative of row `2i`, so the
  rows of any even range of ids (the traffic's 256 prompt ids, the whole
  vocabulary) add up to nothing. Drawn plainly, the 256 prompt ids' rows keep
  a mean a sixteenth of a row's size; layer 0's value rows are a function of
  the token alone, so every head of every sequence adds that same mean of
  them to the residual stream, every router after it sees one direction in
  every row, and an expert's load is that direction's product with its
  router row: 0.29 of an even load in the standard deviation over generated
  rows, 0.16 with the pairs where the count's own noise is 0.09, and the
  held experts read a decode step 61.3-63.0% by the seed against 63.5-64.1%
  (my chip run, PR 61, call 62c: `forward(tell=True)` on three seeds). A
  trained embedding's mean over its vocabulary is near nothing beside a
  row; a draw's over 256 ids is not;
- `wo`, `w2`, `sw2` (what writes to the residual stream): that, times
  `hidden**-0.5 (2 layers)**-0.5`; `wo` times ATTN_OUT_GAIN (3) besides, and
  the routed experts' `w2` times EXPERT_OUT_GAIN (2: a chip that holds 16 of
  256 computes a sixteenth of a layer's routed sum), both `kanana_weights.py`'s;
- `wq_nope`, `wq_pe`: times Q_GAIN (0.5). The latents' rescale (`a_q` 2.24,
  `a_kv` 3.16 or 2.24) multiplies a score by `a_q a_kv`, 7.1 in a full layer
  and 5.0 in a sliding one: at plain scales the scores' standard deviation
  would be about 6, a softmax of one key; at 0.5 it is about 3 (2.2 in a
  sliding layer): a head attends to a few keys of its 2,048 or 513 and
  does not average V, so WHICH rows are among them matters, and
  `rescale_off` is a temperature seven (five) times off;
- `q_norm`, `kv_norm` (the latents' norms): 1 + LATENT_NORM_STD (0.3)
  normal: gains away from 1, so a norm that skips its weight shows;
- `wg` (the gate): plain `hidden**-0.5`: unit-variance logits, gates of
  sigmoid(N(0, 1)): two thirds of them between 0.27 and 0.73, away from 0
  and 1, a head's mean 0.5: `gate_off` doubles what attention adds and
  re-weights its heads;
- `iq`, `ik`, `iw`, `ik_norm`, `ik_bias` (the indexer): `keyevl_weights.py`'s:
  plain projections, signed head weights, a norm whose gains lie away from 1
  and whose bias lies away from 0. `q^I` is made from the rescaled query
  latent, so an index score spreads 2.24 times wider, by a factor that
  changes no choice;
- `ln1`, `ln2`, `norm`: ones; `router`: normal times `hidden**-0.5`,
  `kanana_weights.py`'s (the check compares logits with the program's choice
  of experts given);
- `router_bias` [256] float32: normal times BIAS_STD (0.004), NOT
  `kanana_weights.py`'s 0.03. Among 256 sigmoid scores of unit-variance
  logits the eighth and the ninth lie 0.0064 apart in the mean, and a score
  there moves 0.116 a unit of logit: a bias of 0.03 is a quarter of a logit's
  spread, an expert's share of the tokens then runs from a twentieth of an
  even one to three times it, and what this chip's 16 of 256 draw is the
  seed's: a decode step of 32 rows read 52.3% to 61.9% of the held experts'
  weights by the seed (an even router reads 63.2%), `step_ms.ffn` 3.13 to
  3.53 ms of a 12 ms step, and the cell's tokens a second spread past half
  its bound between seeds (my chip runs, PR 61, and the driver's check of
  it). The published bias is what training left of an auxiliary-loss-free
  balance: its loads ARE even. At 0.004 the bias still changes the eight
  chosen for three rows in ten and by itself leaves an expert's share of the
  tokens within a tenth of even (`tests/acpbench/test_dots_spec.py` holds
  both; with the embedding's pairs above, what the held experts read on the
  chip: PERF.md, PR 61).

A leaf of the dense layers' attention and its twin of the expert full layers' (the same shapes) are ONE draw over
both stacks, split after: the weights' program is 62 leaves and its compile goes by the leaf (22.4 s of this
sandbox's compiler for 17.5), in a cold run that has 360 s. The values are drawn by jax's default keys (threefry). `rbg` keys were tried
for the compile's sake (62 leaves: 22 s of this sandbox's compiler by
threefry, 15 by `rbg`) and cost MORE on the chip's machine, 44.8 s of a cold
run's set-up for 36.9 (my chip runs, PR 61, calls 61f and 61a): taken out.

These arrays are the benchmark's inputs: the engine serves them and
`dots_reference.py` reads the same arrays.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .lfm2_weights import _normal

LATENT_NORM_STD = 0.3
IK_NORM_STD = 0.3
Q_GAIN = 0.5
ATTN_OUT_GAIN = 3.0
EXPERT_OUT_GAIN = 2.0
BIAS_STD = 0.004
RESIDUAL_OUT = ("wo", "w2", "sw2")
OUTPUTS_FIRST = ("embed", "wq_nope", "wq_pe", "wuk")


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
        if name in ("q_norm", "kv_norm"):
            return (1.0 + LATENT_NORM_STD * jax.random.normal(key, shape)).astype(dtype)
        if name in ("ik_norm", "ik_bias"):
            return ((name == "ik_norm") + IK_NORM_STD * jax.random.normal(key, shape)).astype(dtype)
        if name == "router_bias":
            return (BIAS_STD * jax.random.normal(key, shape)).astype(jnp.float32)
        scale = (shape[-1] if name in OUTPUTS_FIRST else shape[-2]) ** -0.5
        if name == "embed":  # a row and its negative (module text)
            half = _normal(key, (-(-shape[0] // 2), shape[1]), scale, dtype)
            return jnp.stack([half, -half], axis=1).reshape(-1, shape[1])[: shape[0]]
        if name in ("wq_nope", "wq_pe"):
            scale *= Q_GAIN
        if name in RESIDUAL_OUT:
            routed = name == "w2" and len(shape) == 4  # [layers, held, F, D]: the routed experts', not the dense layer's
            gain = ATTN_OUT_GAIN if name == "wo" else (EXPERT_OUT_GAIN if routed else 1.0)
            scale *= hidden ** -0.5 * (2 * n_layers) ** -0.5 * gain
        return _normal(key, shape, scale, dtype)

    # the dense layers' attention has the expert full layers' shapes: each such leaf is ONE draw over both stacks,
    # split after (17 of 62 leaves fewer for the compiler, whose time is by the leaf: PERF.md, PR 61)
    nd = schema["dense"]["ln1"].shape[0]
    twins = [n for n, sds in schema["dense"].items() if n in schema["full"] and sds.shape[1:] == schema["full"][n].shape[1:]]
    both = lambda sds: jax.ShapeDtypeStruct((nd + sds.shape[0],) + sds.shape[1:], sds.dtype)  # noqa: E731
    drawn = jax.tree_util.tree_map_with_path(leaf, {
        **schema, "dense": {n: sds for n, sds in schema["dense"].items() if n not in twins},
        "full": {n: both(sds) if n in twins else sds for n, sds in schema["full"].items()}})
    return {**drawn, "dense": {**drawn["dense"], **{n: drawn["full"][n][:nd] for n in twins}},
            "full": {n: a[nd:] if n in twins else a for n, a in drawn["full"].items()}}


def make(program_config, mesh, seed: int):
    """Weights for `program_config` whole on every device of `mesh`."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from agentcontrolplane_tpu.models.dots import init_params

    schema = jax.eval_shape(lambda: init_params(program_config, jax.random.key(0)))
    fn = lambda lo, hi: build(schema, lo, hi, program_config.dim, program_config.n_layers)  # noqa: E731
    lo, hi = jnp.uint32(seed & 0x7FFFFFFF), jnp.uint32(seed >> 31)
    whole = jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), schema)
    return jax.jit(fn, out_shardings=whole)(lo, hi)
