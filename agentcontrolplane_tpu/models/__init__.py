"""Model families, and the one seam through which the engine reaches them.

``programs(config)`` gives the engine a family's serving programs under the
names the dense path has always had (``prefill_paged_batch``,
``prefill_paged_continue``, ``decode_step_paged``, ...), chosen by the type
of the config (``_FAMILIES``: one row a family). The Llama family's entries
are ``models.llama``'s functions themselves. A family that keeps per-slot
state beside the pages (``has_state``: ``models.lfm2``, ``models.jamba``,
``models.nemotron_h``)
receives, where the dense programs take the page ids alone, the pair
``(page_ids, (slots, snap_at))``: which slot's state each row reads and
writes, and where its snapshot is due. What the state is made of stays the
family's: a tree of arrays of whatever types and sizes (``lfm2``: one array
of conv columns; ``jamba``: the recurrence's float32 ``h`` and the conv
columns, two leaves; ``nemotron_h``: the same two, ``S`` of heads in the order
its kernels read). The engine shards whatever tree ``init_paged_cache``
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
that.

A family that drafts by itself (``models.exaone``: the model's own
multi-token-prediction module) gives ``draft_step`` in place of
``decode_step_paged`` in the engine's decode block: ``draft_step(params,
cache, tokens, seq_lens, block_tables, active, sampler, config, ...) ->
(cache, tokens [S, draft_rows], emitted [S], ...)``, a verify-and-draft step
that commits up to ``draft_rows`` tokens a lane, the draft drawn and judged
by the engine's ``sampler`` (``propose`` / ``accept``). What the drafter
carries between steps is the family's, under the cache's ``"state"``.
``draft_step`` None, ``draft_rows`` 1: a step yields one token a lane.

What the engine needs to know of a family beyond its programs it asks here
too, and branches on no family's name or cache kind: ``refusals(asked)``
gives the options a family does not serve, each with its reason in words
(``asked``: what the engine was constructed with; the engine raises on the
first that hits), and ``shardings`` is the family's own layout over a mesh
(``params(mesh, config, abstract)``, ``paged_pool(mesh, quantize_kv)``) or
None: a family that gives none has its weights and its whole cache tree held
whole on every device (``models.kanana`` is such a family with no state a
slot: a latent row a token in the pool, counters beside it; ``models.keye``
is another: a token's K row and V row as one row of the leaf ``kv`` and the
sparse indexer's key a token beside it, ``ik``; ``models.dots`` keeps latent
rows at two ranks, the full layers' and their indexer's keys on the page list
and the sliding layers' in a ring a slot, so it takes ``lanes`` and
``mellum``'s refusals). ``walk(config,
page_rows, dtype, tp, quantize_kv)`` names the compiled walk a decode step
takes on a TPU as ``(pages_per_turn, turns_in_flight, bytes_in_flight)``, or
None where the geometry falls to the XLA reference; ``page_leaf`` names the
leaf of the cache whose pages that walk fetches (the engine reads a page's
rows and dtype off it; a family may keep other leaves beside it, as
``mellum``'s rings, that are not shaped so). The pool's DEPTH is the
family's too, and the leaf's axis 0 is the only place it is read from:
``models.ouro`` runs one stack of layers ``loops`` times over the same
weights and keeps a cache layer a loop and layer (192 over 48 layers of
weights), so ``config.n_layers`` counts its weights and
``Engine.stats()["model"]["cache_layers"]``, the leaf's, its cache;
:func:`page_bytes` gives what a page costs from the pool's own shapes.
"""

from types import SimpleNamespace

from . import dots, exaone, jamba, kanana, keye, lfm2, llama, mellum, nemotron_h, ouro
from .llama import (
    PRESETS,
    LlamaConfig,
    decode_step,
    forward,
    init_kv_cache,
    init_params,
    prefill,
)
from .dots import DotsConfig
from .exaone import ExaoneConfig
from .jamba import JambaConfig
from .kanana import KananaConfig
from .keye import KeyeConfig
from .lfm2 import Lfm2Config
from .mellum import MellumConfig
from .nemotron_h import NemotronHConfig
from .ouro import OuroConfig

__all__ = [
    "PRESETS", "LlamaConfig", "Lfm2Config", "JambaConfig", "MellumConfig", "KananaConfig", "OuroConfig", "ExaoneConfig",
    "NemotronHConfig", "KeyeConfig", "DotsConfig",
    "decode_step", "forward", "init_kv_cache",
    "init_params", "kv_pages_that_fit", "page_bytes", "prefill", "preset", "programs",
]


def preset(name: str):
    """The config a name stands for, in whichever family has it."""
    tables = [module.PRESETS for module in (llama, lfm2, jamba, mellum, kanana, ouro, exaone, nemotron_h, keye, dots)]
    for table in tables:
        if name in table:
            return table[name]
    known = [n for table in tables for n in sorted(table)]
    raise KeyError(f"unknown model preset {name!r}; known: {', '.join(known)}")


def page_bytes(config, page_size: int, quantize_kv: bool = False) -> int:
    """Bytes one page of ``config``'s paged pool costs over every cache layer
    and leaf (scale twins among them), read off the pool the family's own
    ``init_paged_cache`` makes and not off the config's layer count: a family
    may keep more cache layers than layers of weights (``models/ouro.py``),
    or fewer (``lfm2``, ``jamba``: the attention layers alone)."""
    import jax

    from ..ops import paged

    probe = 3  # pages: what tells the page list's leaves from a ring's
    cache = jax.eval_shape(lambda: programs(config).init_paged_cache(
        config, probe, page_size, quantize_kv=quantize_kv, max_slots=1))
    return paged.page_bytes(cache, probe)


def kv_pages_that_fit(config, max_slots: int, max_ctx: int, page_size: int, memory_bytes: int, weight_bytes: int,
                      quantize_kv: bool = False, headroom: float = 0.9) -> int:
    """The pool a paged engine is given where none is asked for: what the
    slots could fill (``max_slots x max_ctx`` tokens and the trash page), cut
    to what ``headroom`` of the device's memory holds beside the weights, at
    :func:`page_bytes` a page. At 1.5 MiB a token the CLI's 64 slots of 2,048
    tokens would ask a 16 GB chip for 206 GB."""
    wanted = max_slots * (max_ctx // page_size) + 1
    fits = int(headroom * memory_bytes - weight_bytes) // page_bytes(config, page_size, quantize_kv)
    return max(2, min(wanted, fits))


def _kv_walk(config, page_rows: int, dtype, tp: int, quantize_kv: bool):
    """The page walk over K and V pages at the config's head geometry."""
    from ..ops.pallas.paged_attention import fetches_in_flight, heads_per_window, pages_per_turn

    heads = config.n_kv_heads // tp
    if not heads_per_window(config.head_dim, heads, quantize_kv):
        return None
    geometry = (page_rows, dtype, heads, config.head_dim, quantize_kv)
    return (pages_per_turn(*geometry), *fetches_in_flight(*geometry))


def _latent_walk(config, page_rows: int, dtype, tp: int, quantize_kv: bool):
    """The latent walk: one leaf, one fetch a page, the row both key and value."""
    from ..ops.pallas.paged_attention import fetches_in_flight, latent_walk_serves, pages_per_turn

    if not latent_walk_serves(config.head_dim, config.kv_lora_rank, page_rows, dtype):
        return None
    geometry = (page_rows, dtype, 1, config.head_dim, False, 1)
    return (pages_per_turn(*geometry), *fetches_in_flight(*geometry))


def _llama_shardings() -> SimpleNamespace:
    """The dense family's own layout over a mesh (``parallel/mesh.py``,
    which imports this package for ``LlamaConfig``: so it is imported when
    a layout is asked for, not while this module is being made)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def params(mesh, config, abstract) -> dict:
        from ..parallel.mesh import param_shardings

        return param_shardings(mesh, config, abstract)

    def paged_pool(mesh, quantize_kv: bool) -> dict:
        # [L, num_pages, page_size, H_kv * d]: the row's KV heads over tp
        # (H_kv / tp heads of d contiguous lanes a chip); within-page over
        # sp (context-parallel paged serving: page ids stay rank-local, each
        # rank holds a slice of every page); int8 scale twins [L, NP, P,
        # H_kv] a chip's heads, like the row
        sp = "sp" if "sp" in mesh.axis_names and dict(mesh.shape)["sp"] > 1 else None
        spec = NamedSharding(mesh, P(None, None, sp, "tp"))
        return {name: spec for name in (("k", "v", "ks", "vs") if quantize_kv else ("k", "v"))}

    return SimpleNamespace(params=params, paged_pool=paged_pool)


def _stateful_refusals(window_cache: bool, drafts: bool = False):
    """What a family with state a slot does not serve; a window cache adds
    what would restore a slot's pages without its ring. A family that
    ``drafts`` speculates over its ring by itself, one row a step; the
    n-gram drafter (``spec_len``) stays refused there."""
    spec_why = ("spec_len > 0: the n-gram drafter's rows (up to spec_len a step) do not fit the ring's one page of "
                "slack; the family drafts one row a step by itself, with no option"
                if drafts else "spec_len > 0: speculation needs the per-slot state rolled back on a rejected draft")

    def refusals(asked: dict) -> list:
        refused = [
            (asked["kv_layout"] != "paged", "kv_layout='slot': its state lives beside the paged pool; serve it with kv_layout='paged'"),
            (asked["spec_len"] > 0, spec_why),
            (asked["tp"] > 1 or asked["sp"] > 1, "tensor or context parallelism: its weights and state have no sharding here; serve it on a tp=1 mesh"),
            (asked["quantize_weights"], "weight-only int8: its matrices are served in the dtype they were made in"),
            (asked["coordination"], "multi-host lockstep serving"),
        ]
        if window_cache:
            # the window layers' ring is rebuilt by a prefill and copied
            # nowhere: whatever would restore a slot's pages without it is
            # refused, so that no slot is ever served with its full-layer
            # pages restored and its ring not
            refused += [
                (asked["host_kv_bytes"] > 0, "host_kv_bytes > 0: a swapped-out slot's window cache is not carried to the host and back"),
                (asked["quantize_kv"], "quantize_kv: its window cache and its pages are kept in the model's dtype"),
            ]
        return refused

    return refusals


_LLAMA = SimpleNamespace(
    family="llama", has_state=False, window_cache=False, counters=None, draft_step=None, draft_rows=1,
    refusals=lambda asked: [], shardings=_llama_shardings(), walk=_kv_walk, page_leaf="k",
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

def _with_state(family: str, m, window_cache: bool = False, page_leaf: str = "k", walk=_kv_walk) -> SimpleNamespace:
    """A family with per-slot state: its module's programs take ``lanes``
    after the page ids; the engine hands both as one pair."""
    draft_step = getattr(m, "verify_step_paged", None)
    return SimpleNamespace(
        family=family, has_state=True, window_cache=window_cache,
        refusals=_stateful_refusals(window_cache, draft_step is not None), shardings=None, walk=walk,
        page_leaf=page_leaf, draft_step=draft_step, draft_rows=getattr(m, "ROWS", 1),
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
# mellum's two caches over kanana's expert layer, and the model's own MTP
# module as the drafter: its decode program is a verify-and-draft step
_EXAONE = _with_state("exaone", exaone, window_cache=True)
# jamba's seam over a state of heads (Mamba-2: 4 MiB a slot and layer) and latent experts; blocks of one mixer
_NEMOTRON_H = _with_state("nemotron_h", nemotron_h)
# no state a slot (its programs take the page ids alone, as the dense
# family's), counters on the device, its own pool: a latent row a token
_KANANA = SimpleNamespace(
    family="kanana", has_state=False, window_cache=False, draft_step=None, draft_rows=1,
    refusals=kanana.refusals, shardings=None, walk=_latent_walk, page_leaf="kv",
    init_params=kanana.init_params, init_paged_cache=kanana.init_paged_cache,
    prefill_paged_batch=kanana.prefill_paged_batch,
    prefill_paged_continue=kanana.prefill_paged_continue,
    prefill_paged_continue_kv=kanana.prefill_paged_continue_kv,
    decode_step_paged=kanana.decode_step_paged,
    counters=kanana.counters, describe_counters=kanana.describe_counters,
)
# the dense family's layer run `loops` times: a pool `loops` times as deep as
# the weights (its leaf's axis 0, `stats()["model"]["cache_layers"]`), the
# dense family's walk at its own head geometry, counters beside the pages.
# Its config derives from LlamaConfig: the MRO finds this row first
_OURO = SimpleNamespace(
    family="ouro", has_state=False, window_cache=False, draft_step=None, draft_rows=1,
    refusals=ouro.refusals, shardings=None, walk=_kv_walk, page_leaf="k",
    init_params=ouro.init_params, init_paged_cache=ouro.init_paged_cache,
    prefill_paged_batch=ouro.prefill_paged_batch,
    prefill_paged_continue=ouro.prefill_paged_continue,
    prefill_paged_continue_kv=ouro.prefill_paged_continue_kv,
    decode_step_paged=ouro.decode_step_paged,
    counters=ouro.counters, describe_counters=ouro.describe_counters,
)
# kanana's seam (no state a slot, counters on the device) over a pool of its own, two leaves: `kv`, a token's K
# row and V row as one row of 32-bit words, and `ik`, the sparse indexer's key a token; its decode step chooses rows and fetches
# them by row through XLA's gather, one slice a chosen position, so it names no compiled walk
_KEYE = SimpleNamespace(
    family="keye", has_state=False, window_cache=False, draft_step=None, draft_rows=1,
    refusals=keye.refusals, shardings=None, walk=lambda *geometry: None, page_leaf="kv",
    init_params=keye.init_params, init_paged_cache=keye.init_paged_cache,
    prefill_paged_batch=keye.prefill_paged_batch,
    prefill_paged_continue=keye.prefill_paged_continue,
    prefill_paged_continue_kv=keye.prefill_paged_continue_kv,
    decode_step_paged=keye.decode_step_paged,
    counters=keye.counters, describe_counters=keye.describe_counters,
)
# mellum's seam (a ring a slot beside the page list, so its refusals) over latent rows at two ranks: the page list
# holds the full layers' latent row `kv` and their indexer's key `ik`, the ring the sliding layers' latent row `wkv`;
# a decode step chooses rows and fetches them by row, and gathers the ring, through XLA: it names no compiled walk
_DOTS = _with_state("dots", dots, window_cache=True, page_leaf="kv", walk=lambda *geometry: None)
_FAMILIES = {LlamaConfig: _LLAMA, Lfm2Config: _LFM2, JambaConfig: _JAMBA, MellumConfig: _MELLUM,
             KananaConfig: _KANANA, OuroConfig: _OURO, ExaoneConfig: _EXAONE, NemotronHConfig: _NEMOTRON_H,
             KeyeConfig: _KEYE, DotsConfig: _DOTS}


def programs(config) -> SimpleNamespace:
    """The family of ``config``'s type, or of the nearest listed type it
    derives from; a config of no listed type is an error, not the dense
    family served without its state."""
    for kind in type(config).__mro__:
        if kind in _FAMILIES:
            return _FAMILIES[kind]
    raise TypeError(f"no model family serves a {type(config).__name__}; "
                    f"known: {', '.join(k.__name__ for k in _FAMILIES)}")
