"""Unit tests for the KV memory-tier primitives in ops/paged.py:
:class:`HostKVPool` (bounded host-RAM tier: LRU within a byte budget,
rid/prefix matching, conservation audit) and the
:class:`PageAllocator` shared-page counter that backs the dedup gauge.
No engine, no jax dispatches — these pin the host-side accounting the
invariant checker audits."""

import numpy as np
import pytest

from agentcontrolplane_tpu.ops.paged import HostKVEntry, HostKVPool, PageAllocator


# the leaves an entry of each kind of pool carries, with the width of a row
# of each: a `k` and a `v` of 8 (H_kv * d), or the one leaf of a latent pool
# (16: key and value at once), the same bytes a token
POOLS = {"k_and_v": {"k": 8, "v": 8}, "one_leaf": {"kv": 16}}
_leaves = POOLS["k_and_v"]


@pytest.fixture(autouse=True, params=sorted(POOLS))
def pool_kind(request):
    """Every case runs over an entry of each kind of pool: the host tier
    takes an entry's leaves as they come."""
    global _leaves
    _leaves = POOLS[request.param]
    yield request.param
    _leaves = POOLS["k_and_v"]


def entry(rid: str, n_tokens: int, toks=None) -> HostKVEntry:
    # [L, T, width]: rows as the paged pool holds them
    return HostKVEntry(
        rid=rid,
        tokens=tuple(toks if toks is not None else range(n_tokens)),
        rows={name: np.zeros((2, n_tokens, width), dtype=np.float32) for name, width in _leaves.items()},
    )


ENTRY_BYTES = entry("x", 8).nbytes  # 2*8*2*4 floats * 2 arrays = 1024


def test_put_get_pop_accounting():
    pool = HostKVPool(10 * ENTRY_BYTES)
    e = entry("r1", 8)
    assert pool.put(e)
    assert pool.used_bytes == e.nbytes and len(pool) == 1
    assert pool.get("r1") is e
    assert pool.get("nope") is None
    used, entries = pool.audit()
    assert used == sum(entries.values()) == e.nbytes
    assert pool.pop("r1") is e
    assert pool.used_bytes == 0 and len(pool) == 0
    assert pool.pop("r1") is None  # idempotent


def test_reput_same_rid_replaces_without_double_count():
    pool = HostKVPool(10 * ENTRY_BYTES)
    pool.put(entry("r1", 8))
    bigger = entry("r1", 16)
    assert pool.put(bigger)
    assert len(pool) == 1
    assert pool.used_bytes == bigger.nbytes


def test_lru_eviction_within_budget():
    pool = HostKVPool(3 * ENTRY_BYTES)
    for rid in ("a", "b", "c"):
        assert pool.put(entry(rid, 8))
    pool.get("a")  # a lookup refreshes recency: "a" is now the hottest
    assert pool.put(entry("d", 8))
    assert pool.get("b") is None  # least-recently-USED evicted, not oldest
    assert pool.get("a") is not None
    assert pool.used_bytes <= pool.max_bytes


def test_match_prefix_refreshes_recency():
    pool = HostKVPool(2 * ENTRY_BYTES)
    pool.put(entry("old", 8, toks=[1] * 8))
    pool.put(entry("new", 8, toks=[2] * 8))
    assert pool.match_prefix([1] * 8 + [3]).rid == "old"  # touches "old"
    pool.put(entry("third", 8, toks=[4] * 8))
    assert pool.get("new") is None  # "new" was the least recently used
    assert pool.get("old") is not None


def test_oversized_entry_refused():
    pool = HostKVPool(ENTRY_BYTES)
    pool.put(entry("small", 8))
    assert not pool.put(entry("huge", 64))
    # the refusal must not have evicted anything to make room
    assert pool.get("small") is not None
    assert pool.used_bytes == ENTRY_BYTES


def test_match_prefix_longest_strict():
    pool = HostKVPool(10 * ENTRY_BYTES)
    pool.put(entry("short", 4, toks=[1, 2, 3, 4]))
    pool.put(entry("long", 8, toks=[1, 2, 3, 4, 5, 6, 7, 8]))
    pool.put(entry("other", 6, toks=[9, 9, 9, 9, 9, 9]))
    row = [1, 2, 3, 4, 5, 6, 7, 8, 10, 11]
    assert pool.match_prefix(row).rid == "long"
    # strict: an entry covering the WHOLE row cannot match (no suffix
    # tokens left to produce logits)
    assert pool.match_prefix([1, 2, 3, 4]) is None
    assert pool.match_prefix([1, 2, 3, 4, 99]).rid == "short"
    assert pool.match_prefix([42]) is None


def test_clear_resets_accounting():
    pool = HostKVPool(10 * ENTRY_BYTES)
    pool.put(entry("a", 8))
    pool.clear()
    assert pool.used_bytes == 0 and len(pool) == 0


# -- PageAllocator.shared_count ----------------------------------------------


def test_shared_count_tracks_refcounts_incrementally():
    alloc = PageAllocator(16)
    pages = alloc.alloc(4)
    assert alloc.shared_count == 0
    alloc.share(pages[:2])  # refcount 2 on two pages
    assert alloc.shared_count == 2
    alloc.share(pages[:1])  # refcount 3: still ONE shared page
    assert alloc.shared_count == 2
    alloc.free(pages[:1])  # 3 -> 2: still shared
    assert alloc.shared_count == 2
    alloc.free(pages[:2])  # page0 2->1, page1 2->1: no longer shared
    assert alloc.shared_count == 0
    alloc.free(pages)  # last refs drop; pool whole again
    assert alloc.free_count == 15
    free_pages, refs = alloc.audit()
    assert len(free_pages) == 15 and refs == {}


def test_shared_count_survives_interleaved_alloc_free():
    alloc = PageAllocator(8)
    a = alloc.alloc(2)
    alloc.share(a)
    b = alloc.alloc(3)
    alloc.free(b)
    assert alloc.shared_count == 2
    alloc.free(a)
    alloc.free(a)
    assert alloc.shared_count == 0
    with pytest.raises(KeyError):  # double-free still loud
        alloc.free(a)
