"""The device's half of the program's spans: what each compiled op is for.

The host's spans are ``acp.<phase>`` (``profiler.py``: admit, launch, fetch,
...); the device's are ``acp.<layer>``, opened with :func:`layer` where the
work is traced. A scope is metadata only: it becomes a component of the
``op_name`` of every HLO instruction traced under it
(``jit(decode_block)/while/body/closed_call/acp.attn/attn_qkv/dot_general``),
the profiler records that path as the op's ``tf_op`` stat, and
``acpbench/device_scopes.py`` sums device time by it. The compiled
instructions are the unscoped program's, so there is nothing to turn off.

Every op of a decode step and of a prefill's or continuation's forward pass
lies under exactly one top-level scope; the innermost ``acp.*`` on a path
wins. Finer names inside one (``attn_qkv``, ``page_walk``, ``moe_route``,
``ssm_update``, ...) are plain ``jax.named_scope`` leaves beside the code.
"""

from __future__ import annotations

import jax

PREFIX = "acp."

LAYERS = {
    "embed": "the token gather",
    "attn": "an attention layer's mixer: norm, q/k/v, rope, the attention operator, wo, residual",
    "mixer": "a token mixer that is not attention: the short conv, the Mamba layer",
    "ffn": "the feed-forward: norm, dense SwiGLU or routed experts, residual",
    "commit": "writes of new rows into a cache outside a layer, and the device counters",
    "head": "the last norm, the output head, the soft cap",
    "sample": "the constraint's mask, the sampler, stop and budget bookkeeping",
}


def layer(name: str):
    """``jax.named_scope("acp." + name)`` for a name of the vocabulary."""
    if name not in LAYERS:
        raise ValueError(f"no device scope {name!r}: the vocabulary is {sorted(LAYERS)}")
    return jax.named_scope(PREFIX + name)
