"""The Jamba family (`models/jamba.py`), dense (`num_experts` 1): Mamba-1
layers with an attention layer every `attn_layer_period` layers, a dense
SwiGLU in every layer.

The file keeps the source's `config.json` keys, and beside them
`layer_types` and `head_dim`, which the source leaves to its convention
(`assumed` says how each is derived). Weights: `jamba_weights.py`, bfloat16
with `A_log`, `D` and `dt_bias` in float32, the one precision this family
draws (`engine.quantize` must be absent). Reference: `jamba_reference.py`;
its controls are `lower="int8"` (every matmul input, K and V rounded),
`"bf16"` (the same to the stated precision; it must pass), `"h_bf16"` (the
recurrence's state carried in bfloat16), `"nonorm"` (the dt, B and C norms
left out) and `"nobias"` (the conv's bias left out).

The cache's own controls (keyword arguments of `cached_logits`), each
applied to what the decode steps read and to nothing a prefill reads, so
that `cache_excess` is what sees them: `h_bf16=True` (the stored `h`
rounded to bfloat16 at the hand-over and after every decode step: the
precision below the one the file states), `zero_state=True` (the state
zeroed between the prompt's prefill and the first decode step),
`state_swap=True` (every slot handed its neighbour's state there) and
`quantize_kv=True` (int8 pages).

**The stored state is read itself** (this family's own two numbers, beside
`check.py`'s four). Sixteen steps of a bfloat16 `h` move the logits by a
twenty-fifth of what the bfloat16 stream moves them, so none of the four can
see the precision the file states for the state (PERF.md section 2). After
the N forced decode steps the B slots' `h` is read:

`state_rel_rms`      the first Mamba layer's `h` against the `h` the
    program's own prefill of the same rows (the prompt plus N tokens) left
    in the same slots, ||decode - prefill|| / ||prefill|| (deeper layers
    carry the stream's rounding besides: 0.013 over all 26). Both paths round
    `u`, `dt` and the x-projection's input to bfloat16 on their own, so a
    sound program reads that rounding (0.002-0.003 on the chip) and a state
    rounded to bfloat16 only 1.4 times it: this number is limited where a
    WRONG state reads (zeroed, a neighbour's: 0.25-0.40), not a rounded one.
`state_16bit_share`  the share of the stored `h`'s nonzero values, every
    Mamba layer's, that a 16-bit float holds exactly (the 13 lowest mantissa
    bits zero). What a float32 recurrence leaves has those bits at random:
    2**-13. A state that rests in bfloat16 or float16 between steps reads 1.
    This is the number that holds the program to `precision.ssm_state`.

The limits are the file's `check.state_limits`. `check.decide` takes no
number from a family (`acpbench/check.py` and `run.py` are not a
`model_config` PR's to edit), so `cached_logits` prints each number beside
its limit as the harness prints its own, and past a limit hands back decode
logits that are not numbers: the run then reads `finite=False` and `correct`
false. When the harness takes a family's numbers into `decide`, that carrier
goes (PERF.md section 7).
"""

from __future__ import annotations

import numpy as np

from .. import check
from . import jamba_reference, jamba_weights


def program_config(config: dict):
    from agentcontrolplane_tpu.models.jamba import JambaConfig

    if config["num_experts"] != 1:
        raise ValueError(f"the jamba family serves the dense model (num_experts 1), not {config['num_experts']}")
    if config["mamba_proj_bias"]:
        raise ValueError("the jamba family has no bias in the Mamba projections (mamba_proj_bias false)")
    return JambaConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        n_heads=config["num_attention_heads"], n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        layer_types=tuple("attention" if t == "full_attention" else t for t in config["layer_types"]),
        ffn_dim=config["intermediate_size"], d_inner=config["mamba_expand"] * config["hidden_size"],
        d_state=config["mamba_d_state"], d_conv=config["mamba_d_conv"], dt_rank=config["mamba_dt_rank"],
        conv_bias=config["mamba_conv_bias"], norm_eps=config["rms_norm_eps"],
        max_seq_len=config["max_position_embeddings"], tie_embeddings=config["tie_word_embeddings"],
    )


def weights(config: dict, program_config, mesh, seed: int):
    precision = config["engine"].get("quantize")
    if precision is not None:
        raise ValueError(f"the jamba family draws bfloat16 weights only; the file's engine.quantize is {precision!r}")
    return jamba_weights.make(program_config, mesh, seed)


def _sizes(config: dict) -> dict:
    """What the plain reference needs, from the file's keys alone."""
    return {
        "n_heads": config["num_attention_heads"], "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"], "norm_eps": config["rms_norm_eps"],
        "d_state": config["mamba_d_state"], "dt_rank": config["mamba_dt_rank"],
        "conv_bias": config["mamba_conv_bias"], "layer_types": tuple(config["layer_types"]),
    }


def reference_logits(config: dict, params, tokens, rows, lower: str | None = None):
    return jamba_reference.logits(params, _sizes(config), tokens, rows, lower=lower)


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
                   quantize_kv: bool = False, zero_state: bool = False, h_bf16: bool = False,
                   state_swap: bool = False):
    """(pre [B, N+1, V], dec [B, N, V], the state's numbers: module text)
    from the program: prefills of the prompt and of the prompt plus 1..N
    forced tokens, then N decode steps from the prompt's prefill, through
    the pages of the attention layers and the Mamba state of sequence b in
    slot b."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from agentcontrolplane_tpu.models.jamba import decode_step_paged, init_paged_cache, prefill_paged_batch

    rep = NamedSharding(mesh, P())
    B = s["B"]
    cache = jax.jit(lambda: init_paged_cache(program_config, s["pool_pages"], s["P"], quantize_kv=quantize_kv,
                                             max_slots=B))()
    put = lambda a: jax.device_put(jnp.asarray(a), rep)  # noqa: E731
    lanes = (put(np.arange(B, dtype=np.int32)), put(np.full(B, -1, np.int32)))
    prefill = jax.jit(
        lambda p, c, t, n, ids: prefill_paged_batch(p, c, t, n, ids, lanes, program_config), donate_argnums=(1,))
    decode = jax.jit(
        lambda p, c, t, n, tb: decode_step_paged(
            p, c, t, n, tb, jnp.ones(t.shape, bool), program_config,
            use_pallas=use_pallas and not quantize_kv, mesh=mesh),
        donate_argnums=(1,))
    T, N, lengths = s["T"], s["N"], s["lengths"]

    def prefilled(extra: int):
        nonlocal cache
        n = lengths + extra
        prompt = np.where(np.arange(T)[None, :] < n[:, None], s["tokens"][:, :T], 0)
        cache, logits = prefill(params, cache, put(prompt), put(n), put(check.page_ids(s, n)))
        return logits.astype(jnp.float32)

    # the longer prefills first, the prompt's own last: it leaves the pages
    # and the state the decode steps go on from
    pre = [prefilled(N)]
    h_prefill = jnp.copy(cache["state"]["ssm"][:1])  # the first layer's, after the prompt plus N tokens, where the steps end
    pre = (pre + [prefilled(j) for j in range(N - 1, -1, -1)])[::-1]
    st = cache["state"]
    if zero_state:
        st["ssm"], st["conv"] = jnp.zeros_like(st["ssm"]), jnp.zeros_like(st["conv"])
    if state_swap:
        st["ssm"] = st["ssm"].at[:, :B].set(jnp.roll(st["ssm"][:, :B], 1, axis=1))
        st["conv"] = st["conv"].at[:, :B].set(jnp.roll(st["conv"][:, :B], 1, axis=1))
    rounded = jax.jit(jamba_reference._round_bf16, donate_argnums=(0,))  # the values bfloat16 would store
    dec = []
    tables = put(s["tables"])
    rows = np.arange(B)
    if h_bf16:
        cache["state"]["ssm"] = rounded(cache["state"]["ssm"])
    for j in range(N):
        cache, logits = decode(params, cache, put(s["tokens"][rows, lengths + j]), put(lengths + j), tables)
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
