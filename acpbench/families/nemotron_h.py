"""The Nemotron-H family (`models/nemotron_h.py`; `model_type: nemotron_h`,
Nemotron 3 Super): blocks of ONE mixer each by `hybrid_override_pattern` (`M`
Mamba-2, `E` LatentMoE, `*` attention), of whose `n_routed_experts` routed
experts the chip holds `num_experts_held` (experts 0 .. held-1).

The file keeps the source's `config.json` keys as published, plus
`num_experts` (= `n_routed_experts`, the name the harness's readers read)
and `num_experts_held`; `assumed` says what the source leaves out. Weights:
`nemotron_h_weights.py`, bfloat16 with `A_log`, `D` and `dt_bias` float32
(`engine.quantize` must be absent). Reference: `nemotron_h_reference.py`,
given the same `held`. Its controls (`lower=`): "int8" (every matmul input,
K and V rounded: the precision below the configuration's in the matmuls),
"bf16" (the same to the stated precision; it must pass), "recurrence_bf16"
(bfloat16 inside the recurrence), "decay_quotient" (a chunk's decays as a
quotient of two exponentials) and "latent_skip" (the latent projection
skipped for the first expert layer).

The cache's own controls (keywords of `cached_logits`), each applied to what
the decode steps read and to nothing a prefill reads, so that `cache_excess`
and the state's own numbers are what see them: `h_bf16=True` (the stored `S`
rounded to bfloat16 at the hand-over and after every decode step: the
precision below the one the file states for it), `zero_state=True` (the
state zeroed between the prompt's prefill and the first decode step),
`state_swap=True` (every slot handed its neighbour's state there),
`recurrence_bf16=True` (bfloat16 INSIDE the decode steps' recurrence: `dt`,
`x`, `B`, `C` rounded on their way into the update and `S` on its way out),
`free_routing=True` (the routing left free on the rows read twice).

The cache check teacher-forces the routing where it forces the tokens, as
`kanana`'s and `exaone`'s do and for their reason: among 512 sigmoid scores
the 22nd and the 23rd lie a few thousandths apart, bfloat16 flips a choice
in some of a hundred (token, layer) pairs, and a flipped choice of a held
expert moves its row far more than all the rounding in it. Every row read
twice is computed with the reference's own choice (`nemotron_h_reference.
route`); the prompt's own prefill, the first of the compared rows, routes
freely, as every token the engine emits does.

**The stored state is read itself**, as `families/jamba.py` reads its `h`
and for its reason (sixteen steps of a bfloat16 state move the logits by a
fraction of what the bfloat16 stream moves them). After the N forced decode
steps the B slots' `S` is read:

`state_rel_rms`      the first Mamba layer's `S` against the `S` the
    program's own prefill of the same rows (the prompt plus N tokens, the
    chunked scan) left in the same slots, ||decode - prefill|| / ||prefill||:
    the update kernel's recurrence against the chunked form's, limited where
    a WRONG state reads (zeroed, a neighbour's).
`state_16bit_share`  the share of the stored `S`'s nonzero values, every
    Mamba layer's, that a 16-bit float holds exactly (the 13 lowest mantissa
    bits zero): 2**-13 of a float32 recurrence's, 1 of a state that rests in
    bfloat16 between steps. This is the number that holds the program to
    `precision.ssm_state`.

The limits are the file's `check.state_limits`. `check.decide` takes no
number from a family, so `cached_logits` prints each beside its limit as the
harness prints its own, and past a limit hands back decode logits that are
not numbers (`families/jamba.py`'s carrier; PERF.md section 7).
"""

from __future__ import annotations

import numpy as np

from .. import check
from . import nemotron_h_reference, nemotron_h_weights

SEQUENCES = 2  # sequences a prefill of the cache check takes


def _held(config: dict) -> tuple:
    return tuple(range(config.get("num_experts_held", config["n_routed_experts"])))


def program_config(config: dict):
    from agentcontrolplane_tpu.models.nemotron_h import NemotronHConfig, pattern

    if config["num_experts"] != config["n_routed_experts"]:
        raise ValueError("num_experts is n_routed_experts under the name the harness's readers read: they differ")
    for key, only in (("n_group", 1), ("topk_group", 1), ("mlp_hidden_act", "relu2"), ("mamba_hidden_act", "silu"),
                      ("use_conv_bias", True), ("mamba_proj_bias", False), ("use_bias", False), ("mlp_bias", False),
                      ("attention_bias", False), ("n_shared_experts", 1), ("num_nextn_predict_layers", 0),
                      ("tie_word_embeddings", False)):
        if config[key] != only:
            raise ValueError(f"the nemotron_h family serves {key}={only!r} only; the file has {config[key]!r}")
    if len(config["hybrid_override_pattern"]) != config["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern is not num_hidden_layers characters")
    if config["expand"] * config["hidden_size"] != config["mamba_num_heads"] * config["mamba_head_dim"]:
        raise ValueError("expand x hidden_size is not mamba_num_heads x mamba_head_dim")
    return NemotronHConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        layer_types=pattern(config["hybrid_override_pattern"]), n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        mamba_heads=config["mamba_num_heads"], mamba_head_dim=config["mamba_head_dim"],
        d_state=config["ssm_state_size"], n_groups=config["n_groups"], d_conv=config["conv_kernel"],
        n_experts=config["n_routed_experts"], experts_per_token=config["num_experts_per_tok"],
        experts_held=_held(config), latent_dim=config["moe_latent_size"],
        expert_ffn_dim=config["moe_intermediate_size"], shared_ffn_dim=config["moe_shared_expert_intermediate_size"],
        norm_topk_prob=config["norm_topk_prob"], routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_eps=config["layer_norm_epsilon"], max_seq_len=config["max_position_embeddings"],
        tie_embeddings=config["tie_word_embeddings"],
    )


def weights(config: dict, program_config, mesh, seed: int):
    precision = config["engine"].get("quantize")
    if precision is not None:
        raise ValueError(f"the nemotron_h family draws bfloat16 weights only; the file's engine.quantize is {precision!r}")
    steps = (config["time_step_min"], config["time_step_max"], config["time_step_floor"])
    return nemotron_h_weights.make(program_config, mesh, seed, steps)


def _sizes(config: dict) -> dict:
    """What the plain reference needs, from the file's keys alone."""
    return {
        "pattern": config["hybrid_override_pattern"], "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"], "head_dim": config["head_dim"],
        "mamba_heads": config["mamba_num_heads"], "mamba_head_dim": config["mamba_head_dim"],
        "d_state": config["ssm_state_size"], "n_groups": config["n_groups"], "norm_eps": config["layer_norm_epsilon"],
        "experts_per_token": config["num_experts_per_tok"], "held": _held(config),
        "norm_topk_prob": config["norm_topk_prob"], "routed_scaling_factor": float(config["routed_scaling_factor"]),
    }


def reference_logits(config: dict, params, tokens, rows, lower: str | None = None):
    return nemotron_h_reference.logits(params, _sizes(config), tokens, rows, lower=lower)


def cached_logits(config: dict, program_config, params, mesh, s: dict, use_pallas: bool, **control):
    """`cache_readings`' logits, held to the file's limits on the stored
    state (module text): past one the decode logits come back not numbers."""
    import jax.numpy as jnp

    pre, dec, state = cache_readings(config, program_config, params, mesh, s, use_pallas, **control)
    good = True
    for name, limit in config["check"]["state_limits"].items():
        within = bool(state[name] <= limit)
        good = good and within
        print(f"[check] {name}={state[name]:.6g} limit={limit:.6g} {'ok' if within else 'EXCEEDED'}", flush=True)
    return pre, dec if good else jnp.full_like(dec, jnp.nan)


def cache_readings(config: dict, program_config, params, mesh, s: dict, use_pallas: bool,
                   zero_state: bool = False, h_bf16: bool = False, state_swap: bool = False,
                   recurrence_bf16: bool = False, free_routing: bool = False):
    """(pre [B, N+1, V], dec [B, N, V], the state's numbers: module text)
    from the program: prefills of the prompt and of the prompt plus 1..N
    forced tokens, then N decode steps from the prompt's prefill, through
    the attention layer's pages and the Mamba state of sequence b in slot b.
    `pre[:, 0]` routes freely; `pre[:, 1:]`, `dec` and the prefill that leaves
    the state the decode steps go on from take the reference's choice of
    experts (module text)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from agentcontrolplane_tpu.models import nemotron_h as program
    from agentcontrolplane_tpu.models.nemotron_h import decode_step_paged, init_paged_cache, prefill_paged_batch

    low = nemotron_h_reference._round_bf16
    update = program.ssd.update

    def update_in_bf16(state, row, dt, x, b, c, a, **kw):
        y, state = update(state, row, low(dt), low(x), low(b), low(c), a, **kw)
        return y, low(state)

    rep = NamedSharding(mesh, P())
    B = s["B"]
    cache = jax.jit(lambda: init_paged_cache(program_config, s["pool_pages"], s["P"], max_slots=B))()
    put = lambda a: jax.device_put(jnp.asarray(a), rep)  # noqa: E731
    T, N, lengths = s["T"], s["N"], s["lengths"]
    forced = not free_routing
    # [expert layers, B, T + N, k]: the reference's choice for every token
    route = np.asarray(nemotron_h_reference.route(params, _sizes(config), s["tokens"])) if forced else None

    def prefill(p, c, t, n, ids, slots, route=None):
        return prefill_paged_batch(p, c, t, n, ids, (slots, jnp.full(slots.shape, -1, jnp.int32)), program_config,
                                   route=route)

    def step(p, c, t, n, tb, route=None):
        return decode_step_paged(p, c, t, n, tb, jnp.ones(t.shape, bool), program_config, use_pallas=use_pallas,
                                 mesh=mesh, route=route)

    def decode_lowered(*args):  # `step` traced with the program's update swapped for the control's, and put back
        program.ssd.update = update_in_bf16
        try:
            return step(*args)
        finally:
            program.ssd.update = update

    prefill = jax.jit(prefill, donate_argnums=(1,))
    decode = jax.jit(decode_lowered if recurrence_bf16 else step, donate_argnums=(1,))

    def prefilled(extra: int, given: bool):
        nonlocal cache
        n = lengths + extra
        prompt = np.where(np.arange(T)[None, :] < n[:, None], s["tokens"][:, :T], 0)
        ids = check.page_ids(s, n)
        out = []
        for b in range(0, B, SEQUENCES):
            # one compiled shape: a last dispatch short of sequences is filled with empty rows in the slot nothing reads
            real = np.arange(b, b + SEQUENCES) < B
            rows = np.minimum(np.arange(b, b + SEQUENCES), B - 1)
            args = (params, cache, put(prompt[rows].astype(np.int32)), put(np.where(real, n[rows], 0).astype(np.int32)),
                    put(np.where(real[:, None], ids[rows], 0).astype(np.int32)), put(np.where(real, rows, B).astype(np.int32)))
            if given and forced:
                cache, logits = prefill(*args, put(route[:, rows, :T].astype(np.int32)))
            else:
                cache, logits = prefill(*args)
            out.append(logits.astype(jnp.float32)[: int(real.sum())])
        return jnp.concatenate(out, axis=0)

    # the longest prefill first (its state is where the decode steps end), then the shorter ones; then the
    # prompt's own twice: routed freely for its logits, and with the routing given to leave pages and state as
    # a request of `lengths` tokens would, where the decode steps go on from
    pre = [prefilled(N, True)]
    h_prefill = jnp.copy(cache["state"]["ssm"][:1])  # the first layer's, after the prompt plus N tokens
    pre = (pre + [prefilled(j, True) for j in range(N - 1, 0, -1)])[::-1]
    pre.insert(0, prefilled(0, False))
    if forced:
        prefilled(0, True)
    st = cache["state"]
    if zero_state:
        st["ssm"], st["conv"] = jnp.zeros_like(st["ssm"]), jnp.zeros_like(st["conv"])
    if state_swap:
        st["ssm"] = st["ssm"].at[:, :B].set(jnp.roll(st["ssm"][:, :B], 1, axis=1))
        st["conv"] = st["conv"].at[:, :B].set(jnp.roll(st["conv"][:, :B], 1, axis=1))
    rounded = jax.jit(nemotron_h_reference._round_bf16, donate_argnums=(0,))  # the values bfloat16 would store
    if h_bf16:
        cache["state"]["ssm"] = rounded(cache["state"]["ssm"])
    dec = []
    tables = put(s["tables"])
    rows = np.arange(B)
    for j in range(N):
        given = (put(route[:, rows, lengths + j][:, :, None].astype(np.int32)),) if forced else ()
        cache, logits = decode(params, cache, put(s["tokens"][rows, lengths + j].astype(np.int32)),
                               put((lengths + j).astype(np.int32)), tables, *given)
        if h_bf16:  # at rest between steps the state is what bfloat16 holds
            cache["state"]["ssm"] = rounded(cache["state"]["ssm"])
        dec.append(logits.astype(jnp.float32))
    apart = lambda got, want: float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))  # noqa: E731
    h_decode = cache["state"]["ssm"][:, :B]
    low_bits = jax.lax.bitcast_convert_type(h_decode, jnp.uint32) & jnp.uint32(0x1FFF)
    held = h_decode != 0
    state = {"state_rel_rms": apart(h_decode[0], h_prefill[0, :B]),
             "state_16bit_share": float(jnp.sum(held & (low_bits == 0)) / jnp.maximum(jnp.sum(held), 1))}
    return jnp.stack(pre, axis=1), jnp.stack(dec, axis=1), state
