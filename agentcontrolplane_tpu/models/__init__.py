"""Model families, and the one seam through which the engine reaches them.

``programs(config)`` gives the engine a family's serving programs under the
names the dense path has always had (``prefill_paged_batch``,
``prefill_paged_continue``, ``decode_step_paged``, ...), chosen by the type
of the config (``_FAMILIES``: one row a family). The Llama family's entries
are ``models.llama``'s functions themselves. A family that keeps per-slot
state beside the pages (``has_state``: ``models.lfm2``, ``models.jamba``)
receives, where the dense programs take the page ids alone, the pair
``(page_ids, (slots, snap_at))``: which slot's state each row reads and
writes, and where its snapshot is due. What the state is made of stays the
family's: a tree of arrays of whatever types and sizes (``lfm2``: one array
of conv columns; ``jamba``: the recurrence's float32 ``h`` and the conv
columns, two leaves). The engine shards whatever tree ``init_paged_cache``
puts under ``"state"`` whole, and moves a slot's part as a tree too:
``saved_state(cache, slot)`` gives it, ``install_state(cache, slot, tree)``
takes it back, and between the two the engine only keeps it (on the device
in a prefix entry, as numpy leaves in a host entry). A family that counts on
the device gives ``counters(cache)`` (the array to read, inside a program)
and ``describe_counters(config, total) -> {stats key: dict}`` for
``Engine.stats()``; ``counters=None`` keeps none. The two capabilities are
apart: state without counters and counters without state both serve.
``window_cache`` (``models.mellum``) says that the family keeps a second
cache a slot, the window layers' ring, fixed to the slot and written by the
programs alone: it takes ``lanes`` as a family with state does, but nothing
of it can be saved or installed, so the engine refuses what would need
that (``engine.py``'s list).
"""

from types import SimpleNamespace

from . import jamba, lfm2, llama, mellum
from .llama import (
    PRESETS,
    LlamaConfig,
    decode_step,
    forward,
    init_kv_cache,
    init_params,
    prefill,
)
from .jamba import JambaConfig
from .lfm2 import Lfm2Config
from .mellum import MellumConfig

__all__ = [
    "PRESETS", "LlamaConfig", "Lfm2Config", "JambaConfig", "MellumConfig", "decode_step", "forward", "init_kv_cache",
    "init_params", "prefill", "preset", "programs",
]


def preset(name: str):
    """The config a name stands for, in whichever family has it."""
    tables = [module.PRESETS for module in (llama, lfm2, jamba, mellum)]
    for table in tables:
        if name in table:
            return table[name]
    known = [n for table in tables for n in sorted(table)]
    raise KeyError(f"unknown model preset {name!r}; known: {', '.join(known)}")


_LLAMA = SimpleNamespace(
    family="llama", has_state=False, window_cache=False, counters=None,
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

def _with_state(family: str, m, window_cache: bool = False) -> SimpleNamespace:
    """A family with per-slot state: its module's programs take ``lanes``
    after the page ids; the engine hands both as one pair."""
    return SimpleNamespace(
        family=family, has_state=True, window_cache=window_cache,
        init_params=m.init_params,
        init_paged_cache=m.init_paged_cache,
        prefill_paged_batch=lambda params, cache, tokens, lengths, ids, config: (
            m.prefill_paged_batch(params, cache, tokens, lengths, ids[0], ids[1], config)),
        prefill_paged_continue=lambda params, cache, tokens, lengths, starts, ids, tables, config: (
            m.prefill_paged_continue(params, cache, tokens, lengths, starts, ids[0], tables, ids[1], config)),
        prefill_paged_continue_kv=lambda params, cache, tokens, lengths, starts, ids, tables, config: (
            m.prefill_paged_continue_kv(params, cache, tokens, lengths, starts, ids[0], tables, ids[1], config)),
        decode_step_paged=m.decode_step_paged,
        install_state=m.install_state, saved_state=m.saved_state,
        counters=m.counters, describe_counters=m.describe_counters,
    )


_LFM2 = _with_state("lfm2", lfm2)
_JAMBA = _with_state("jamba", jamba)
_MELLUM = _with_state("mellum", mellum, window_cache=True)
_FAMILIES = {LlamaConfig: _LLAMA, Lfm2Config: _LFM2, JambaConfig: _JAMBA, MellumConfig: _MELLUM}


def programs(config) -> SimpleNamespace:
    """The family of ``config``'s type, or of the nearest listed type it
    derives from; a config of no listed type is an error, not the dense
    family served without its state."""
    for kind in type(config).__mro__:
        if kind in _FAMILIES:
            return _FAMILIES[kind]
    raise TypeError(f"no model family serves a {type(config).__name__}; "
                    f"known: {', '.join(k.__name__ for k in _FAMILIES)}")
