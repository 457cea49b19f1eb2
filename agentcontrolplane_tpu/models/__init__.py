"""Model families, and the one seam through which the engine reaches them.

``programs(config)`` gives the engine a family's serving programs under the
names the dense path has always had (``prefill_paged_batch``,
``prefill_paged_continue``, ``decode_step_paged``, ...), chosen by the type
of the config. The Llama family's entries are ``models.llama``'s functions
themselves. A family that keeps per-slot state beside the pages
(``has_state``: ``models.lfm2``) receives, where the dense programs take
the page ids alone, the pair ``(page_ids, (slots, snap_at))``: which slot's
state each row reads and writes, and where its snapshot is due. What the
state is made of stays the family's: the engine shards whatever tree
``init_paged_cache`` puts under ``"state"`` whole, and copies a slot's part
through ``install_state`` / ``saved_state``. A family that counts on the
device gives ``counters(cache)`` (the array to read, inside a program) and
``describe_counters(config, total) -> (stats key, dict)`` for
``Engine.stats()``; ``counters=None`` keeps none. The two capabilities are
apart: state without counters and counters without state both serve.
"""

from types import SimpleNamespace

from . import llama, lfm2
from .llama import (
    PRESETS,
    LlamaConfig,
    decode_step,
    forward,
    init_kv_cache,
    init_params,
    prefill,
)
from .lfm2 import Lfm2Config

__all__ = [
    "PRESETS", "LlamaConfig", "Lfm2Config", "decode_step", "forward", "init_kv_cache",
    "init_params", "prefill", "preset", "programs",
]


def preset(name: str):
    """The config a name stands for, in whichever family has it."""
    for table in (llama.PRESETS, lfm2.PRESETS):
        if name in table:
            return table[name]
    known = sorted(llama.PRESETS) + sorted(lfm2.PRESETS)
    raise KeyError(f"unknown model preset {name!r}; known: {', '.join(known)}")


_LLAMA = SimpleNamespace(
    family="llama", has_state=False, counters=None,
    init_params=llama.init_params,
    init_kv_cache=llama.init_kv_cache, prefill_batch=llama.prefill_batch,
    prefill_continue=llama.prefill_continue, prefill_continue_kv=llama.prefill_continue_kv,
    verify_continue=llama.verify_continue, decode_step=llama.decode_step,
    init_paged_cache=lambda config, num_pages, page_size, quantize_kv=False, max_slots=1: (
        llama.init_paged_cache(config, num_pages, page_size, quantize_kv=quantize_kv)),
    prefill_paged_batch=llama.prefill_paged_batch,
    prefill_paged_continue=llama.prefill_paged_continue,
    prefill_paged_continue_kv=llama.prefill_paged_continue_kv,
    verify_paged_continue=llama.verify_paged_continue,
    decode_step_paged=llama.decode_step_paged,
)

_LFM2 = SimpleNamespace(
    family="lfm2", has_state=True,
    init_params=lfm2.init_params,
    init_paged_cache=lfm2.init_paged_cache,
    prefill_paged_batch=lambda params, cache, tokens, lengths, ids, config: (
        lfm2.prefill_paged_batch(params, cache, tokens, lengths, ids[0], ids[1], config)),
    prefill_paged_continue=lambda params, cache, tokens, lengths, starts, ids, tables, config: (
        lfm2.prefill_paged_continue(params, cache, tokens, lengths, starts, ids[0], tables, ids[1], config)),
    prefill_paged_continue_kv=lambda params, cache, tokens, lengths, starts, ids, tables, config: (
        lfm2.prefill_paged_continue_kv(params, cache, tokens, lengths, starts, ids[0], tables, ids[1], config)),
    decode_step_paged=lfm2.decode_step_paged,
    install_state=lfm2.install_state, saved_state=lfm2.saved_state,
    counters=lfm2.counters, describe_counters=lfm2.describe_counters,
)


def programs(config) -> SimpleNamespace:
    return _LFM2 if isinstance(config, Lfm2Config) else _LLAMA
