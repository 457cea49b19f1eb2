"""Pallas TPU kernel: paged decode attention.

One new token per slot attends over its page list. The kernel walks each
sequence's block table (scalar-prefetched so page indices are known before
the body runs), DMAs K/V pages HBM -> VMEM through a ring of buffers, and
accumulates a flash-style online softmax — the gathered
``[S, max_ctx, H, d]`` copy the pure-XLA reference materializes
(``ops.paged.paged_decode_attention_reference``) never exists.

The kernel emits the UNNORMALIZED accumulator state ``(acc, m, l)`` per
slot; normalization — and, in the serving hot loop, the not-yet-written new
token's self-attention term — merges outside in (fused) XLA. That keeps the
cache pages a read-only operand: the engine's decode step commits all
layers' new K/V with one scatter after the layer scan instead of writing
pages before every attention call (see models/llama.py decode_step_paged).

The kernel reads pages ``[num_pages, P, H_kv * d]``, a row its KV heads side
by side, and that is how every family's pool is stored (``ops/paged.py``):
a program hands the walk its whole pool flattened over the layers, with
block tables offset by the layer, and nothing is relaid. A test or
``chip_smoke.py`` may still hand the wrappers one layer's ``[num_pages, P,
H_kv, d]``; the merge is then a copy of those pages on the chip's tiling.

One program walks every slot (or as many as fit VMEM with their q and
outputs: ``slots_per_program``), as ONE stream of turns. A turn of the walk
covers G pages, G chosen so that a turn is one 128-lane tile of tokens
(``pages_per_turn``: 8 at page 16, 1 at page 128; a pool of one leaf: 32): the turn's
pages are DMA'd each into its own row window of one ``[2, G * P, H_kv * d]``
buffer (K and V) and the body runs one pair of products per KV head over all
of them. The stream is every slot's turns in slot order, slots with nothing
to walk stepped over; the fetches run ``RING - 1`` turns ahead of the fold
from the kernel's first turn to its last, so a slot's last turns are folded
while the next slots' first are in flight and the queue never drains between
slots (``fetches_in_flight``). What a turn costs on a v5e, as measured with
the kernel's parts taken out (PERF.md, PR 43): ~13 cycles of the scalar
unit to issue each fetch, two a page, which may not be scheduled past a load
of the buffers they write to; the latency of the body's two
passes (product, reductions, product), which is the same for one KV head as
for four; and ~27 ns a fetch in the DMA engine whatever its size, which is
the floor under 16-row pages. A fetch's latency is NOT it (rings of 2 to 16
buffers walk equally fast), nor are the bytes. So: a turn's 2 x G fetches
signal one semaphore and are taken by ONE wait of the buffer's size (a turn
always fetches G pages; past the walk's last page it fetches that page
again); and the next fetches are issued unguarded, half after each of the
body's two passes, where they cost least. Working set: the ring,
RING x 2 (K+V) x [G * P, H_kv * d] — 1 MB for Qwen2.5-7B geometry (page 16,
4 KV heads, d 128, bf16) — beside the program's q and three outputs.

Geometry note: the walk takes head widths 64, 128 and 256
(``heads_per_window``): a multiple of the 128-lane width whole (128 for
llama/qwen/mistral, 256 for gemma), and 64 two KV heads to a lane window
(LFM2; bf16 or f32 pages, an even number of KV heads a chip: the wrapper
lays each pair's queries out on their own lanes of one query group, the
pool keeps its bytes and its layout). The engine falls back to the XLA
reference otherwise. The body is a static loop over the KV heads: each
takes its lane-aligned ``[G * P, d]`` column window of the turn's buffer and
two plain 2-D products with its ``[n_rep, d]`` query group. Every shape in the
body is 2-D because that is what Mosaic lays out — the earlier grouped
form (``p.reshape(P, H_kv, n_rep)[..., None] * v[:, :, None, :]``) passed
every interpret-mode test and was refused by the chip's compiler at every
head ratio ("infer-vector-layout: unsupported shape cast"), and a batched
matvec trips a Mosaic dot-dimension bug. q and the (acc, m, l) outputs
cross the kernel boundary grouped ``[S, H_kv, n_rep, .]`` for the same
reason; the wrapper reshapes them outside.

int8 page walk: with ``k_scales``/``v_scales`` (the allocator's per-row-
per-head f32 scale twins, [num_pages, P, H_kv] as the pool stores them, or
already laid out for the kernel by :func:`walk_scale_rows`) each page
fetch also DMAs its scale rows, on a semaphore of their own. The per-row
scale factors out of both products — ``q . (k_int8 * s) == (q . k_int8) *
s`` — so the body scales the ``[n_rep, P]`` logits and softmax weights by
the head's ``[1, P]`` scale row and never builds a dequantized page; the
pool stays int8 in HBM and only int8 bytes cross to VMEM. The scale rows
reach the kernel head-major and padded to whole 128-lane rows (built by
XLA outside the kernel, :func:`scale_rows`): Mosaic cannot slice an HBM
operand whose minor dim is under a lane tile. That transpose touches every
scale it is given, so a program that calls the walk once a layer over its
whole pool builds the rows once a step, outside its layer scan
(:func:`walk_scale_rows`, ``scales_laid=True``).

Latent walk (``value_width``, the call named ``paged_latent_walk``): the
pool has ONE leaf, a row a token that is key and value at once (latent
attention, ``models/kanana.py``: 512 values of normed latent and 64 of the
roped shared key). A page is ONE fetch into a ``[G * P, row]`` buffer, every
query head scores against the whole row (one KV "head" of the row's width, a
query group of all the heads) and the value is the row's first
``value_width`` columns of the same buffer: ``acc += p R[:, :512]``, ``p`` a
bf16 head and a bf16 tail over bf16 pages (two passes, exact in the pages'
values, 2**-17 in ``p``; 60 FLOP a byte, so not the f32 contract's six).
Same stream of turns, same (acc, m, l) contract. What a turn costs on a v5e
(16 slots of ~3,600 rows, 48 layers, cold pages; PERF.md, PR 45): at ONE
lane tile of rows 0.45 us, 2.5 times its bytes' time, the body alone 0.43
and the fetches alone 0.26. The body is one chain (product, max, exp, sum,
two products, rescale) where a K/V turn runs one a KV head side by side;
neither the MXU's passes nor the key tile's transpose hold it (PR 44). So a
turn is ``LATENT_TILES`` lane tiles (32 pages): four independent score
products, one max, exp, sum and rescale for all, 0.26 us a 128 rows: the
fetches' own time (8 at ~27 ns in the DMA engine, and their issue). The tail
(a slot's last turn fetches its last page again) shows under 128 rows a slot.

A verify step (:func:`paged_verify_attention_cache_plus_new`, ``R`` query
rows a lane at positions ``seq_lens + r``; ``models/exaone.py``): the rows
are rows of the walk's query group, not lanes of the walk. q crosses the
boundary as ``[S, H_kv, R * n_rep, d]`` (a KV head's queries of row 0, then
of row 1) beside the lane's own table and length, so a page is fetched
ONCE for all of a lane's rows and a turn's two products serve them all (16
rows a KV head at 64 / 8 heads and two rows: one whole bf16 sublane tile,
where a group of 8 was padded to it). Over the full-attention pages every
row of a lane sees the same page rows (the new rows are folded in outside
the kernel) and the body is the one-row body. Over a ring each row has its
own edge, one position after the row's before it: ``starts_ref`` holds
``rows`` edges a slot, the walk begins at the page of the first, and
``valid`` is a ``[R * n_rep, T]`` select between the edges over a row iota; a
query row whose edge lies past a turn's rows adds nothing to its ``l``.
``rows`` is static and the branch a Python one: at ``rows == 1`` the body
traces what it traced before there was one.

Tested in interpreter mode on CPU against the exact reference
(tests/engine/test_paged*.py, tests/engine/test_exaone.py), compiled for a
described v5e (tests/engine/test_chip_compile.py, test_exaone_compile.py),
and run compiled on the chip against the reference (chip_smoke.py,
tests/engine/test_tpu_hardware.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
RING = 4  # turn buffers of the walk: RING - 1 turns' fetches run ahead of the fold
LANES = 128  # a turn of the walk over K and V pages covers one lane tile of tokens
LATENT_TILES = 4  # and over a pool of one leaf this many: its turn is one chain (module text)
# scratch the walk may claim of the 16 MiB scoped VMEM a kernel gets by
# default; the rest is the body's f32 windows and a program's q and outputs
_SCRATCH_BUDGET = 8 << 20
_SLOTS_BUDGET = 3 << 20  # of one program's q and outputs (each held twice)
# In-kernel products run at f32 contract precision: the walk is the same
# f32 math as the XLA reference, not a bf16-pass approximation of it. The
# one exception is exact: q . k with both sides bf16 (see `scores`).
_F32 = jax.lax.Precision.HIGHEST


def pages_per_turn(P_local: int, dtype, H_kv: int, d: int, quantized: bool = False, leaves: int = 2) -> int:
    """G, the pages one turn of the walk fetches and folds, from what the
    kernel can see alone: as many as make a turn one lane tile of tokens, or
    ``LATENT_TILES`` tiles over a pool of ONE leaf (``leaves=1``: a latent row).

    G = 1 (a page a turn, each page DMA'd into a whole buffer) where a page
    cannot land on a whole-tile row window of a shared buffer — its rows
    must be a multiple of the dtype's sublane tile: 8 for f32, 16 for bf16,
    32 for int8 — and for int8 pages at any size: their scale rows are laid
    out head-major per page and do not follow a G-page turn. G halves until
    the ring of buffers (K and V, or the one leaf) fits ``_SCRATCH_BUDGET``.
    """
    itemsize = jnp.dtype(dtype).itemsize
    G = max(1, (LATENT_TILES if leaves == 1 else 1) * LANES // P_local)
    if quantized or P_local % (32 // itemsize):
        G = 1
    while G > 1 and leaves * RING * G * P_local * H_kv * d * itemsize > _SCRATCH_BUDGET:
        G //= 2
    return G


def fetches_in_flight(P_local: int, dtype, H_kv: int, d: int, quantized: bool = False,
                      leaves: int = 2) -> tuple[int, int]:
    """(turns, bytes) of K and V (``leaves``: 1 for a latent row) the compiled walk keeps started
    ahead of the turn it folds, from its first turn to its last and across slots: ``RING - 1`` turns
    of ``pages_per_turn`` pages. Not scaled by the turn's bytes: on a v5e the walk is bound by what
    a turn's fetches cost the scalar unit to issue and by its two passes' latency, not by a fetch's
    latency over the bytes in flight, and rings of 3, 6, 8 and 16 buffers all measured slower than
    4 (PERF.md, PR 43; over one leaf 3, 5 and 6 no faster: PR 45)."""
    G = pages_per_turn(P_local, dtype, H_kv, d, quantized, leaves)
    return RING - 1, (RING - 1) * leaves * G * P_local * H_kv * d * jnp.dtype(dtype).itemsize


def slots_per_program(S: int, H_kv: int, n_rep: int, d: int, q_dtype) -> int:
    """Slots one program of the walk streams through: all ``S`` where their
    q and three outputs fit ``_SLOTS_BUDGET`` of VMEM (held twice, pipelined
    from program to program), else the largest divisor of ``S`` that does.
    m and l pad their last axis to a lane tile, q's rows to its dtype's."""
    itemsize = jnp.dtype(q_dtype).itemsize
    tile = 32 // itemsize  # rows of q's sublane tile; float32's is 8
    q_rows, rows = -(-n_rep // tile) * tile, -(-n_rep // 8) * 8
    slot = H_kv * (q_rows * d * itemsize + rows * d * 4 + 2 * rows * LANES * 4)
    return next(b for b in range(S, 0, -1) if S % b == 0 and (b * slot <= _SLOTS_BUDGET or b == 1))


def heads_per_window(d: int, H_kv: int, quantized: bool = False) -> int:
    """KV heads that share one 128-lane window of the page buffer: 1 at the
    widths the body slices whole (d % 128 == 0), 128 // d at a narrower
    width that divides a lane tile and pairs its heads up evenly (64: two a
    window), 0 where the walk does not go: another width, an odd head
    count, or int8 pages at a narrow width (a window's rows would need each
    head's own scale row)."""
    if d % LANES == 0:
        return 1
    if LANES % d == 0 and d >= 64 and H_kv % (LANES // d) == 0 and not quantized:
        return LANES // d
    return 0


def scale_rows(scales: jax.Array) -> jax.Array:
    """Scale twins ``[num_pages, P, H_kv]`` as the kernel reads them,
    ``[num_pages, 1, SC]``: a page's scales head-major (``[H_kv, P]``
    flattened) and padded to whole 128-lane rows. Mosaic cannot slice an HBM
    operand whose minor dim is under a lane tile, and the body wants each
    head's scales as a ``[1, P]`` row."""
    num_pages, P, H_kv = scales.shape
    SC = -(-H_kv * P // LANES) * LANES
    rows = scales.astype(jnp.float32).transpose(0, 2, 1).reshape(num_pages, 1, H_kv * P)
    return jnp.pad(rows, ((0, 0), (0, 0), (0, SC - H_kv * P)))


def _mesh_axes(mesh) -> tuple[int, int]:
    """(tp, sp) of a mesh; (1, 1) of none."""
    if mesh is None:
        return 1, 1
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return axes.get("tp", 1), axes.get("sp", 1)


def _laid_scale_spec(sp: int):
    from jax.sharding import PartitionSpec as P

    return P(None, None, ("sp", "tp") if sp > 1 else "tp")


def walk_scale_rows(scales: jax.Array, mesh=None) -> jax.Array:
    """:func:`scale_rows` of every chip's own scales (KV heads over ``tp``,
    a page's rows over ``sp``), for the walk's ``scales_laid=True``: what a
    decode step does once, outside its layer scan, over the pool's scales
    flattened over the layers, where the walk called a layer at a time with
    ``[L * num_pages, P, H_kv]`` would do it ``L`` times."""
    tp, sp = _mesh_axes(mesh)
    if tp == 1 and sp == 1:
        return scale_rows(scales)
    from jax.sharding import PartitionSpec as P

    return jax.shard_map(
        scale_rows, mesh=mesh, in_specs=P(None, "sp" if sp > 1 else None, "tp"),
        out_specs=_laid_scale_spec(sp), check_vma=False,
    )(scales)


def _kernel(
    # scalar prefetch
    block_tables_ref,  # [S, max_pages] int32 (SMEM)
    seq_lens_ref,  # [S] int32 (SMEM)
    pos_base_ref,  # [1] int32 (SMEM) — this rank's within-page offset
    # inputs
    q_ref,  # [S, H_kv, n_rep, d] (VMEM) — the program's slots, grouped by KV head
    k_pages_ref,  # [num_pages, P_local, H_kv * d] (HBM/ANY)
    v_pages_ref,  # [num_pages, P_local, H_kv * d]
    # quantized=True only: ks_pages_ref / vs_pages_ref
    #   [num_pages, 1, SC] f32 (HBM/ANY) — a page's per-row-per-head
    #   scales, head-major ([H_kv, P_local] flattened), lane-padded to SC
    # outputs, grouped by KV head like q:
    # acc_ref: [S, H_kv, n_rep, d] f32 — unnormalized weighted V sum
    # m_ref:   [S, H_kv, n_rep, 1] f32 — running max
    # l_ref:   [S, H_kv, n_rep, 1] f32 — running denominator
    # scratch
    # kv_buf: [RING, 2, G * P_local, H_kv * d] (VMEM) — a turn's K and V
    # quantized=True only (G == 1): sc_buf [RING, 2, 1, SC] f32 (VMEM)
    # sems: DMA sems [RING, 2 if quantized else 1] — one a turn (and its scales)
    # turns_ref, next_ref: [S] int32 (SMEM) — a slot's turns, the next slot that has any
    *rest,
    page_size: int,  # GLOBAL page size (pages hold this many tokens)
    quantized: bool = False,
    head_dim: int | None = None,  # the model's, where heads share a lane window
    starts_ref=None,  # [S * rows] int32 (SMEM): a slot's first valid row (the window walk)
    rows: int = 1,  # > 1: a verify step's window walk, the group `rows` blocks of query rows, an edge each
    ring: int = 0,  # > 0: the table is a ring, page a of the sequence at a % ring
    value_width: int = 0,  # > 0: the latent walk; no v_pages_ref, V is the row's first columns
):
    # int8 walk (quantized=True): pages hold int8 values plus f32 scale
    # twins (one scale per row per KV head). A turn's fetch DMAs its page's
    # scale rows alongside it on a semaphore of their own and the body
    # applies the scales in VMEM (see `scores`), so int8 decode takes the
    # kernel path with the same (acc, m, l) contract as the f32 walk.
    if quantized:
        (ks_pages_ref, vs_pages_ref, acc_ref, m_ref, l_ref,
         kv_buf, sc_buf, sems, turns_ref, next_ref) = rest
    else:
        acc_ref, m_ref, l_ref, kv_buf, sems, turns_ref, next_ref = rest
        ks_pages_ref = vs_pages_ref = sc_buf = None
    S, n_kv_heads, n_rep, d = q_ref.shape  # S: this program's slots
    base = pl.program_id(0) * S  # its first slot among the batch's
    P = k_pages_ref.shape[1]  # local slice length
    pos_base = pos_base_ref[0]
    D, _, T, _ = kv_buf.shape  # T = G * P tokens a turn
    G = T // P

    def walk(s):
        """(seq_len, first_row, first_page, n_pages) of slot ``s``'s walk.

        Under context-parallel serving each rank holds a [P_local = P/sp]
        slice of every page (pos_base = rank * P_local); the walk length and
        token positions are computed with the GLOBAL page size so masking is
        exact, while DMAs and compute touch only the local slice. sp=1 runs
        with pos_base=0 and P_local == page_size.

        The window walk (``starts_ref``): rows before a slot's first valid
        row are not the query's to see. The walk begins at the page that
        holds that row, skips every page before it, and masks the rows of
        that first page that lie before the edge; ``first_page`` and
        ``n_pages`` are then of the walk and not of the sequence. With
        ``ring`` the table has ``ring`` entries a slot and page ``a`` of the
        sequence sits at ``a % ring``: a window of at most ``(ring - 1)``
        pages of rows touches no entry twice. With ``rows`` edges a slot the
        walk begins at the first of them, which is the lowest (a verify step's
        rows lie one position apart); ``turn`` masks each query row by its own."""
        seq_len = seq_lens_ref[base + s]
        n_pages = jax.lax.div(seq_len + page_size - 1, page_size)
        first_row = first_page = None
        if starts_ref is not None:
            first_row = starts_ref[(base + s) * rows if rows > 1 else base + s]
            first_page = jax.lax.div(first_row, page_size)
            n_pages = jnp.maximum(n_pages - first_page, 0)
        return seq_len, first_row, first_page, n_pages

    # The walk is ONE stream of (slot, turn) items over every slot (of this
    # program: `slots_per_program`) that has a turn, in slot order: the
    # fetches run D - 1 items ahead of the fold from the program's first
    # item to its last, so the queue a slot's last turns leave is already
    # refilled with the next slots' first. Slots with nothing to walk (a
    # padding lane, a window not reached) are never visited: `next_ref`
    # steps over them; their outputs are the start state written here.
    def count(i, c):
        nxt, total = c
        s = S - 1 - i
        n = jax.lax.div(walk(s)[3] + G - 1, G)
        turns_ref[s] = n
        next_ref[s] = nxt
        return jnp.where(n > 0, s, nxt), total + n

    first, total = jax.lax.fori_loop(0, S, count, (jnp.int32(S), jnp.int32(0)))
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)

    def advance(s, t):  # the item after (s, t); s == S past the last
        last = t + 1 >= turns_ref[s]
        return jnp.where(last, next_ref[s], s), jnp.where(last, 0, t + 1)

    def start(s, t, buf, pages=range(G)):
        # A turn's fetches (those of `pages`): page t*G+g of slot s's walk
        # into rows [g*P, (g+1)*P) of the buffer's K and V windows, every
        # one signalling the buffer's one semaphore. A turn always fetches G
        # pages, with no branch among them: past the walk's last page it
        # fetches that page again (a live page, read a moment before), so a
        # turn's bytes are always the buffer's and ONE wait of the buffer's
        # size takes them all, and no row of a buffer is ever left unwritten.
        _, _, first_page, n_pages = walk(s)
        for g in pages:
            at = jnp.minimum(t * G + g, n_pages - 1)
            if starts_ref is not None:
                at = first_page + at
            page = block_tables_ref[base + s, jax.lax.rem(at, ring) if ring else at]
            rows = pl.ds(g * P, P)
            pltpu.make_async_copy(k_pages_ref.at[page], kv_buf.at[buf, 0, rows], sems.at[buf, 0]).start()
            if not value_width:
                pltpu.make_async_copy(v_pages_ref.at[page], kv_buf.at[buf, 1, rows], sems.at[buf, 0]).start()
            if quantized:
                pltpu.make_async_copy(ks_pages_ref.at[page], sc_buf.at[buf, 0], sems.at[buf, 1]).start()
                pltpu.make_async_copy(vs_pages_ref.at[page], sc_buf.at[buf, 1], sems.at[buf, 1]).start()

    def wait(buf):  # every fetch of the turn in `buf`: one wait of its size
        pltpu.make_async_copy(kv_buf.at[buf], kv_buf.at[buf], sems.at[buf, 0]).wait()
        if quantized:
            pltpu.make_async_copy(sc_buf.at[buf], sc_buf.at[buf], sems.at[buf, 1]).wait()

    def ramp(j, c):
        start(*c, j)
        return advance(*c)

    ahead = jax.lax.fori_loop(0, jnp.minimum(D - 1, total), ramp, (first, jnp.int32(0)))

    scale = 1.0 / ((head_dim or d) ** 0.5)
    # q . k in one bf16 MXU pass where both sides are bf16 (int8 widens to
    # bf16 exactly): bf16 x bf16 products are exact in the f32 accumulator,
    # so with 1/sqrt(d) applied to the f32 logits this is the f32 product.
    # f32 q or pages take the f32 contract with q pre-scaled.
    one_pass = q_ref.dtype == jnp.bfloat16 and kv_buf.dtype in (jnp.bfloat16, jnp.int8)

    # token position of a turn's column c, less the turn's first: row c % P
    # of the turn's page c // P, pages page_size tokens apart (sp=1: c)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    col = lane
    if page_size != P:
        for g in range(1, G):
            col = col + jnp.where(lane >= g * P, page_size - P, 0)
    if rows > 1:
        # the group's rows in `rows` blocks a KV head of the window (a head's
        # `per` queries of verify row 0, then of row 1, ...): block b is verify
        # row b % rows and sees from that row's own edge on
        per = n_rep // (rows * (d // (head_dim or d)))
        group_row = jax.lax.broadcasted_iota(jnp.int32, (n_rep, 1), 0)

    def turn(i, carry, issue):
        """Fold item i, slot `s`'s turn `t`, out of buffer i % D; with
        `issue`, start the item D - 1 ahead into the buffer item i - 1 left."""
        (s, t), fetching, carried = carry
        buf = jax.lax.rem(i, D)
        seq_len, first_row, first_page, _ = walk(s)
        wait(buf)
        pos = t * (G * page_size) + pos_base + col
        if starts_ref is not None:
            pos = pos + first_page * page_size
        valid = pos < seq_len  # [1, T]
        if rows > 1:
            edge = first_row
            for b in range(1, n_rep // per):
                edge = jnp.where(group_row >= b * per, starts_ref[(base + s) * rows + b % rows], edge)
            valid = valid & (pos >= edge)  # [n_rep, T]
        elif starts_ref is not None:
            valid = valid & (pos >= first_row)
        if quantized:
            ks = sc_buf[buf, 0]  # [1, >= H_kv * P], head-major
            vs = sc_buf[buf, 1]
        fresh = t == 0  # a slot's first turn starts from the empty state

        # Static loops over the KV heads, in two passes with the next
        # fetches issued between them. Each head takes its lane-aligned
        # [T, d] column window of the turn's buffer (d % 128 == 0) and two
        # plain 2-D products with its [n_rep, d] query group — the only
        # shapes in the body are 2-D, which is what Mosaic lays out (a 4-D
        # grouped reshape of the logits is refused: "unsupported shape cast").
        def scores(h):
            m, l, _ = carried[h]  # [n_rep,1], [n_rep,1], [n_rep,d]
            m = jnp.where(fresh, NEG_INF, m)
            l = jnp.where(fresh, 0.0, l)
            k = kv_buf[buf, 0, :, h * d:(h + 1) * d]  # [T, d]
            if one_pass:
                logits = jax.lax.dot_general(
                    q_ref[s, h], k.astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale  # [n_rep, T]
            else:
                logits = jax.lax.dot_general(
                    q_ref[s, h].astype(jnp.float32) * scale, k.astype(jnp.float32),
                    (((1,), (1,)), ((), ())),
                    precision=_F32, preferred_element_type=jnp.float32,
                )
            if quantized:
                # the per-row scale factors out of both products: scale the
                # [n_rep, P] logits and weights by this head's [1, P] scale
                # row, never the [P, d] page. Masked rows (stale scales)
                # stay finite, so the pos mask zeroes their weight as in f32.
                logits = logits * ks[:, h * P:(h + 1) * P]
            logits = jnp.where(valid, logits, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(logits, axis=1, keepdims=True))
            p = jnp.exp(logits - m_new)  # [n_rep, T]
            if rows > 1:  # a query row that has seen no row yet (its edge past this turn's) adds nothing
                p = jnp.where(valid, p, 0.0)
            correction = jnp.exp(m - m_new)  # [n_rep, 1]
            l = l * correction + jnp.sum(p, axis=1, keepdims=True)
            pw = p * vs[:, h * P:(h + 1) * P] if quantized else p
            return m_new, l, correction, pw

        def values(h, m_new, l, correction, pw):
            if value_width and kv_buf.dtype == jnp.bfloat16:
                # the latent walk (module text): p as a bf16 head and tail,
                # two passes against the row's own bf16 values
                v = kv_buf[buf, 0, :, :value_width]
                head = pw.astype(jnp.bfloat16)
                tail = (pw - head.astype(jnp.float32)).astype(jnp.bfloat16)
                pv = (jnp.dot(head, v, preferred_element_type=jnp.float32)
                      + jnp.dot(tail, v, preferred_element_type=jnp.float32))
            else:
                v = (kv_buf[buf, 0, :, :value_width] if value_width
                     else kv_buf[buf, 1, :, h * d:(h + 1) * d]).astype(jnp.float32)
                pv = jnp.dot(
                    pw, v, precision=_F32, preferred_element_type=jnp.float32
                )  # [n_rep, d]
            acc = jnp.where(fresh, 0.0, carried[h][2])
            return m_new, l, acc * correction + pv

        # The fetch of the item D - 1 ahead, into the buffer item i - 1
        # left, half after each pass and unguarded: a fetch is ~13 cycles of
        # the scalar unit and may not be scheduled past a load of the
        # buffers it writes to, so its place is after a pass's loads, in the
        # shadow of that pass's products and reductions (the study in
        # PERF.md, PR 43: all of them before the first pass cost a turn
        # 0.23 us, so placed 0.06). Unguarded because a branch would end
        # the block they are scheduled in: the `issue` loop runs only over
        # items that have one D - 1 ahead.
        ahead_buf = jax.lax.rem(i + D - 1, D)
        half = [scores(h) for h in range(n_kv_heads)]
        if issue:
            start(*fetching, ahead_buf, range(0, G - G // 2))
        state = tuple(values(h, *half[h]) for h in range(n_kv_heads))
        if issue:
            start(*fetching, ahead_buf, range(G - G // 2, G))
            fetching = advance(*fetching)

        @pl.when(t + 1 >= turns_ref[s])
        def _():  # the slot's last turn: its state is the result
            for h, (m, l, acc) in enumerate(state):
                acc_ref[s, h] = acc
                m_ref[s, h] = m
                l_ref[s, h] = l

        return advance(s, t), fetching, state

    empty = tuple(
        (
            jnp.full((n_rep, 1), NEG_INF, dtype=jnp.float32),
            jnp.zeros((n_rep, 1), dtype=jnp.float32),
            jnp.zeros((n_rep, value_width or d), dtype=jnp.float32),
        )
        for _ in range(n_kv_heads)
    )
    carry = ((first, jnp.int32(0)), ahead, empty)
    fed = jnp.maximum(total - (D - 1), 0)  # items with one D - 1 ahead of them
    carry = jax.lax.fori_loop(0, fed, functools.partial(turn, issue=True), carry)
    jax.lax.fori_loop(fed, total, functools.partial(turn, issue=False), carry)


def _window_kernel(block_tables_ref, seq_lens_ref, pos_base_ref, starts_ref, *rest, **kw):
    """``_kernel`` with a fourth prefetched scalar row: each slot's first
    valid row."""
    _kernel(block_tables_ref, seq_lens_ref, pos_base_ref, *rest, starts_ref=starts_ref, **kw)


def _latent_kernel(block_tables_ref, seq_lens_ref, pos_base_ref, q_ref, pages_ref, *rest, **kw):
    """``_kernel`` over a pool of one leaf: there are no V pages."""
    _kernel(block_tables_ref, seq_lens_ref, pos_base_ref, q_ref, pages_ref, None, *rest, **kw)


def _paged_state(
    q: jax.Array,  # [S, H, d]
    k_pages: jax.Array,  # [num_pages, P_local, H_kv * d] (or [.., H_kv, d])
    v_pages: jax.Array,
    block_tables: jax.Array,  # [S, max_pages] int32
    seq_lens: jax.Array,  # [S] int32
    interpret: bool = False,
    pos_base: jax.Array | None = None,  # [1] int32 — sp rank's page offset
    global_page_size: int | None = None,  # tokens per page (sp>1: > P_local)
    k_scales: jax.Array | None = None,  # [num_pages, P_local, H_kv] f32
    v_scales: jax.Array | None = None,  # (int8 pages: per-row-per-head)
    head_dim: int | None = None,  # softmax scale's width where it is not d
    kv_heads: int | None = None,  # of pages given merged
    scales_laid: bool = False,  # the scales are scale_rows' output already
    starts: jax.Array | None = None,  # [S * rows] int32: the window walk's first valid row a slot (and row)
    ring: int = 0,  # with `starts`: the table is a ring of this many pages a slot
    rows: int = 1,  # a KV head's queries are `rows` blocks (a verify step's rows), each with its own edge
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Run the kernel -> unnormalized (acc [S,H,d] f32, m [S,H], l [S,H]).

    With ``starts`` it is the window walk (named ``paged_window_walk``): a
    slot's rows ``starts[s] .. seq_lens[s] - 1`` and no others, the pages
    before the first skipped and not read. With ``rows`` > 1 a KV head's
    ``H / H_kv`` queries are ``rows`` blocks of as many (the rows of a verify
    step, one query group over one fetch of the slot's pages) and block ``j``
    sees from ``starts[s * rows + j]`` on, ``starts[s * rows]`` the lowest.

    With ``k_scales``/``v_scales`` ([num_pages, P, H_kv] as the pool stores
    them, or ``scales_laid``: [num_pages, 1, SC] from :func:`scale_rows`)
    the pages are int8 and the kernel DMAs each page's scale row alongside
    the page fetch; applying them in VMEM keeps int8's HBM-bandwidth win.
    """
    S, H, d = q.shape
    # pages come merged as the kernel reads them, the layout every pool is
    # stored in (never relaid), or one layer's [num_pages, P, H_kv, d]
    num_pages, P = k_pages.shape[:2]
    H_kv = k_pages.shape[2] if k_pages.ndim == 4 else kv_heads
    pack = heads_per_window(d, H_kv, k_scales is not None)
    if pack > 1:
        # Narrow heads: `pack` KV heads share one lane tile of the page
        # buffer, which stays [G * P, H_kv * d] untouched. The kernel walks
        # H_kv / pack windows of 128 lanes; each window's query group holds
        # its heads' queries on their own lanes and zeros on the others', so
        # one [pack * n_rep, 128] x [128, G * P] product gives every head's
        # logits exactly (the zeros add nothing), each row its own softmax.
        # p . v then fills all 128 lanes of every row; a row's own head's
        # lanes are picked out here, outside the kernel.
        r = H // H_kv
        W = H_kv // pack
        eye = jnp.eye(pack, dtype=q.dtype)
        q_w = jnp.einsum("swjrc,jl->swjrlc", q.reshape(S, W, pack, r, d), eye)
        acc, m, l = _paged_state(
            q_w.reshape(S, W * pack * r, pack * d),
            k_pages.reshape(num_pages, P, W, pack * d),
            v_pages.reshape(num_pages, P, W, pack * d),
            block_tables, seq_lens, interpret, pos_base, global_page_size,
            head_dim=d, starts=starts, ring=ring, rows=rows,
        )
        acc = jnp.einsum("swjrlc,jl->swjrc", acc.reshape(S, W, pack, r, pack, d),
                         jnp.eye(pack, dtype=acc.dtype))
        return acc.reshape(S, H, d), m, l
    n_rep = H // H_kv
    if pos_base is None:
        pos_base = jnp.zeros((1,), dtype=jnp.int32)
    quantized = k_scales is not None

    windowed = starts is not None
    kernel = functools.partial(
        _window_kernel if windowed else _kernel,
        page_size=global_page_size or P,
        quantized=quantized,
        head_dim=head_dim,
        **({"ring": ring} if windowed else {}),
        **({"rows": rows} if windowed and rows > 1 else {}),
    )

    # A program walks as many slots as fit (all of them in every cell: the
    # stream never drains between them), so their q and outputs sit in
    # VMEM together, pipelined from program to program.
    blk = slots_per_program(S, H_kv, n_rep, d, q.dtype)

    def per_program(*tail):
        return pl.BlockSpec(
            (blk, H_kv, n_rep) + tail, lambda c, *_: (c, 0, 0, 0),
            memory_space=pltpu.VMEM,
        )

    in_specs = [
        per_program(d),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    G = pages_per_turn(P, k_pages.dtype, H_kv, d, quantized)
    scratch_shapes = [pltpu.VMEM((RING, 2, G * P, H_kv * d), k_pages.dtype)]
    operands = [
        block_tables,
        seq_lens,
        pos_base.astype(jnp.int32),
        *([starts.astype(jnp.int32)] if windowed else []),
        q.reshape(S, H_kv, n_rep, d),
        k_pages.reshape(num_pages, P, H_kv * d),
        v_pages.reshape(num_pages, P, H_kv * d),
    ]
    if quantized:
        in_specs += [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ]
        if not scales_laid:
            k_scales, v_scales = scale_rows(k_scales), scale_rows(v_scales)
        SC = k_scales.shape[2]
        scratch_shapes.append(pltpu.VMEM((RING, 2, 1, SC), jnp.float32))
        operands += [k_scales, v_scales]
    scratch_shapes += [
        pltpu.SemaphoreType.DMA((RING, 2 if quantized else 1)),
        pltpu.SMEM((blk,), jnp.int32),
        pltpu.SMEM((blk,), jnp.int32),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 if windowed else 3,
        grid=(S // blk,),
        in_specs=in_specs,
        out_specs=[per_program(d), per_program(1), per_program(1)],
        scratch_shapes=scratch_shapes,
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((S, H_kv, n_rep, d), jnp.float32),
            jax.ShapeDtypeStruct((S, H_kv, n_rep, 1), jnp.float32),
            jax.ShapeDtypeStruct((S, H_kv, n_rep, 1), jnp.float32),
        ],
        interpret=interpret,
        # the trace tells the two walks apart by name
        name="paged_window_walk" if windowed else "paged_page_walk",
    )(*operands)
    return acc.reshape(S, H, d), m.reshape(S, H), l.reshape(S, H)


def paged_decode_attention(
    q: jax.Array,  # [S, H, d]
    k_pages: jax.Array,  # [num_pages, P, H_kv * d] with ``kv_heads``, or [.., H_kv, d]
    v_pages: jax.Array,
    block_tables: jax.Array,  # [S, max_pages] int32
    seq_lens: jax.Array,  # [S] int32 — valid tokens per slot (already written)
    interpret: bool = False,
    *,
    k_scales: jax.Array | None = None,  # [num_pages, P, H_kv] f32 — int8 pages
    v_scales: jax.Array | None = None,
    kv_heads: int | None = None,
) -> jax.Array:
    """Attention over written pages only (the classic form)."""
    acc, _m, l = _paged_state(
        q, k_pages, v_pages, block_tables, seq_lens, interpret,
        k_scales=k_scales, v_scales=v_scales, kv_heads=kv_heads,
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def _fold_self_term(q, k_new, v_new, acc, m, l) -> jax.Array:
    """One more online-softmax fold: merge the not-yet-written new token's
    self-attention term into the kernel's unnormalized (acc, m, l) state and
    normalize. Fused elementwise by XLA."""
    S, H, d = q.shape
    H_kv = k_new.shape[1]
    r = H // H_kv
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    q4 = q.reshape(S, H_kv, r, d).astype(jnp.float32)
    self_logit = (
        jnp.sum(q4 * k_new.astype(jnp.float32)[:, :, None, :], axis=-1) * scale
    ).reshape(S, H)
    m2 = jnp.maximum(m, self_logit)
    corr = jnp.exp(m - m2)
    p_self = jnp.exp(self_logit - m2)
    l2 = l * corr + p_self
    v_new_rep = (
        v_new.astype(jnp.float32)[:, :, None, :]
        .repeat(r, axis=2)
        .reshape(S, H, d)
    )
    out = (acc * corr[..., None] + p_self[..., None] * v_new_rep) / jnp.maximum(
        l2, 1e-30
    )[..., None]
    return out.astype(q.dtype)


def paged_decode_attention_cache_plus_new(
    q: jax.Array,  # [S, H, d]
    k_pages: jax.Array,  # [num_pages, P, H_kv * d] (or [.., H_kv, d]) — WITHOUT the new token
    v_pages: jax.Array,
    block_tables: jax.Array,
    seq_lens: jax.Array,  # [S] — tokens valid in the PAGES (excl. new)
    k_new: jax.Array,  # [S, H_kv, d]
    v_new: jax.Array,
    interpret: bool = False,
    *,
    k_scales: jax.Array | None = None,  # [num_pages, P, H_kv] f32 — int8 pages
    v_scales: jax.Array | None = None,
    scales_laid: bool = False,  # the scales come from walk_scale_rows
    starts: jax.Array | None = None,  # [S]: the window walk (`_paged_state`)
    ring: int = 0,
) -> jax.Array:
    """Kernel over the read-only pages + the new token's self term, merged
    outside the kernel. The new token's k/v stay full-precision (they are
    not yet written to pages), so no scales apply to the self term."""
    acc, m, l = _paged_state(
        q, k_pages, v_pages, block_tables, seq_lens, interpret,
        k_scales=k_scales, v_scales=v_scales, kv_heads=k_new.shape[1],
        scales_laid=scales_laid, starts=starts, ring=ring,
    )
    return _fold_self_term(q, k_new, v_new, acc, m, l)


def paged_verify_attention_cache_plus_new(
    q: jax.Array,  # [S, R, H, d]: R query rows a lane, row r at position seq_lens + r
    k_pages: jax.Array,  # [num_pages, P, H_kv * d] — WITHOUT the new rows
    v_pages: jax.Array,
    block_tables: jax.Array,  # [S, max_pages]: one table for all of a lane's rows
    seq_lens: jax.Array,  # [S] — tokens valid in the PAGES (excl. the new rows)
    k_new: jax.Array,  # [S, R, H_kv, d]
    v_new: jax.Array,
    interpret: bool = False,
    *,
    new_valid: jax.Array | None = None,  # [S, R] bool: a new row that is no key
    starts: jax.Array | None = None,  # [S, R]: the window walk, each row's own edge (row 0's the lowest)
    ring: int = 0,
) -> jax.Array:
    """A verify step's attention: a lane's ``R`` rows ride ONE query group of
    the walk over the lane's one table, ``R * H / H_kv`` rows a KV head, so a
    page is fetched once for all of them (over a ring each row is masked by
    its own edge, ``starts[s, 0]`` the lowest); then the new rows folded in
    outside the kernel, row ``r`` over new rows ``0 .. r``: the second row
    sees the first's K/V, which no page holds yet. -> [S, R, H, d]."""
    S, R, H, d = q.shape
    H_kv = k_new.shape[2]
    r = H // H_kv
    acc, m, l = _paged_state(
        q.reshape(S, R, H_kv, r, d).swapaxes(1, 2).reshape(S, R * H, d),  # [S, H_kv x (R, r), d]
        k_pages, v_pages, block_tables, seq_lens, interpret,
        kv_heads=H_kv, starts=None if starts is None else starts.reshape(S * R), ring=ring, rows=R,
    )
    rowwise = lambda a: a.reshape(S, H_kv, R, r, *a.shape[2:]).swapaxes(1, 2)  # noqa: E731 -> [S, R, H_kv, r, .]
    acc, m, l = rowwise(acc), rowwise(m), rowwise(l)
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    q5 = q.reshape(S, R, H_kv, r, d).astype(jnp.float32)
    fresh = jnp.einsum("sjkrd,sikd->sjikr", q5, k_new.astype(jnp.float32)) * scale  # row j over new row i
    seen = jnp.arange(R)[None, :, None] >= jnp.arange(R)[None, None, :]
    if new_valid is not None:
        seen = seen & new_valid[:, None, :]
    fresh = jnp.where(seen[..., None, None], fresh, NEG_INF)
    m2 = jnp.maximum(m, jnp.max(fresh, axis=2))
    corr = jnp.exp(m - m2)
    p_new = jnp.where(seen[..., None, None], jnp.exp(fresh - m2[:, :, None]), 0.0)
    l2 = l * corr + jnp.sum(p_new, axis=2)
    out = acc * corr[..., None] + jnp.einsum(
        "sjikr,sikd->sjkrd", p_new, v_new.astype(jnp.float32))
    out = out / jnp.maximum(l2, 1e-30)[..., None]
    return out.reshape(S, R, H, d).astype(q.dtype)


def latent_walk_serves(width: int, value_width: int, P: int, dtype) -> bool:
    """Whether the compiled latent walk takes this geometry: a page's rows a
    whole sublane tile of its dtype (a turn's pages share one buffer), the
    value a whole number of lane tiles at the row's start, and what is left
    of the row (the shared roped key) narrower than a tile or whole tiles."""
    return (P % (32 // jnp.dtype(dtype).itemsize) == 0 and value_width % LANES == 0
            and 0 < value_width <= width)


def paged_latent_state(
    q: jax.Array,  # [S, H, width]: every head's query against the whole row
    pages: jax.Array,  # [num_pages, P, width]: the pool's one leaf
    block_tables: jax.Array,  # [S, max_pages] int32
    seq_lens: jax.Array,  # [S] int32
    value_width: int,  # the row's first columns are the value
    score_dim: int,  # softmax scale: score_dim ** -0.5
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The latent walk -> unnormalized (acc [S, H, value_width] f32, m [S,
    H], l [S, H]): one fetch a page, ``s = Q R^T``, the online softmax,
    ``acc += p R[:, :value_width]``."""
    S, H, width = q.shape
    num_pages, P = pages.shape[:2]
    G = pages_per_turn(P, pages.dtype, 1, width, leaves=1)
    blk = slots_per_program(S, 1, H, width, q.dtype)

    def per_program(*tail):
        return pl.BlockSpec((blk, 1, H) + tail, lambda c, *_: (c, 0, 0, 0), memory_space=pltpu.VMEM)

    acc, m, l = pl.pallas_call(
        functools.partial(_latent_kernel, page_size=P, head_dim=score_dim, value_width=value_width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S // blk,),
            in_specs=[per_program(width), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[per_program(value_width), per_program(1), per_program(1)],
            scratch_shapes=[
                pltpu.VMEM((RING, 1, G * P, width), pages.dtype),
                pltpu.SemaphoreType.DMA((RING, 1)),
                pltpu.SMEM((blk,), jnp.int32),
                pltpu.SMEM((blk,), jnp.int32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((S, 1, H, value_width), jnp.float32),
            jax.ShapeDtypeStruct((S, 1, H, 1), jnp.float32),
            jax.ShapeDtypeStruct((S, 1, H, 1), jnp.float32),
        ],
        interpret=interpret,
        name="paged_latent_walk",  # no reader of page_walk or paged_window_walk matches it
    )(block_tables, seq_lens, jnp.zeros((1,), jnp.int32), q.reshape(S, 1, H, width), pages)
    return acc.reshape(S, H, value_width), m.reshape(S, H), l.reshape(S, H)


def paged_latent_attention_cache_plus_new(
    q: jax.Array,  # [S, H, width]
    pages: jax.Array,  # [num_pages, P, width] — WITHOUT the new token
    block_tables: jax.Array,
    seq_lens: jax.Array,  # [S] — tokens valid in the PAGES (excl. new)
    row_new: jax.Array,  # [S, width]: the new token's row, not yet written
    value_width: int,
    score_dim: int,
    interpret: bool = False,
) -> jax.Array:
    """The latent walk over the read-only pages plus the new token's own
    term, merged outside the kernel (:func:`_fold_self_term`'s fold, with
    the one row every head shares) -> [S, H, value_width] in q's dtype."""
    acc, m, l = paged_latent_state(q, pages, block_tables, seq_lens, value_width, score_dim, interpret)
    row = row_new.astype(jnp.float32)
    self_logit = jnp.einsum("shw,sw->sh", q.astype(jnp.float32), row) * score_dim ** -0.5
    m2 = jnp.maximum(m, self_logit)
    corr, p_self = jnp.exp(m - m2), jnp.exp(self_logit - m2)
    out = acc * corr[..., None] + p_self[..., None] * row[:, None, :value_width]
    return (out / jnp.maximum(l * corr + p_self, 1e-30)[..., None]).astype(q.dtype)


def _shard_wrap(fn, mesh, interpret, extra_sharded=(), with_scales=False, **kw):
    """``fn`` over each chip's own KV heads: the merged row splits over
    ``tp`` as ``H_kv / tp`` heads of ``d`` contiguous lanes, and a chip's
    scales are its heads' whether as stored or laid out for the kernel."""
    from jax.sharding import PartitionSpec as P

    q_spec = P(None, "tp", None)
    pages_spec = P(None, None, "tp")
    in_specs = (q_spec, pages_spec, pages_spec, P(None, None), P(None)) + extra_sharded
    if with_scales:
        # ``interpret`` sits before the scale params in the wrapped
        # signatures, so map the two trailing positionals back to keywords
        # instead of partial()ing
        scale_spec = P(None, None, "tp")
        in_specs = in_specs + (scale_spec, scale_spec)
        body = lambda q, kp, vp, bt, sl, *rest: fn(  # noqa: E731
            q, kp, vp, bt, sl, *rest[:-2],
            interpret=interpret, k_scales=rest[-2], v_scales=rest[-1], **kw,
        )
    else:
        body = functools.partial(fn, interpret=interpret, **kw)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=q_spec,
        check_vma=False,
    )


def paged_decode_attention_sharded(
    mesh,
    q: jax.Array,  # [S, H, d] — heads sharded over 'tp'
    k_pages: jax.Array,  # [num_pages, P, H_kv * d] — the row's KV heads over 'tp'
    v_pages: jax.Array,
    block_tables: jax.Array,  # replicated
    seq_lens: jax.Array,  # replicated
    interpret: bool = False,
    *,
    kv_heads: int,  # H_kv, of the whole mesh
    k_scales: jax.Array | None = None,  # [num_pages, P, H_kv] — heads over 'tp'
    v_scales: jax.Array | None = None,
) -> jax.Array:
    """tp>1 wrapper: GSPMD treats pallas_call as opaque, so we shard_map it —
    each shard runs the kernel over its local head slice (attention is
    head-parallel; page tables are shared), no collectives needed."""
    local = kv_heads // _mesh_axes(mesh)[0]
    if k_scales is not None:
        return _shard_wrap(
            paged_decode_attention, mesh, interpret, with_scales=True, kv_heads=local
        )(q, k_pages, v_pages, block_tables, seq_lens, k_scales, v_scales)
    return _shard_wrap(paged_decode_attention, mesh, interpret, kv_heads=local)(
        q, k_pages, v_pages, block_tables, seq_lens
    )


def paged_decode_attention_cache_plus_new_sp_sharded(
    mesh,
    q: jax.Array,  # [S, H, d] — heads over 'tp', replicated over 'sp'
    k_pages: jax.Array,  # [num_pages, P, H_kv * d] — P over 'sp', the row's heads 'tp'
    v_pages: jax.Array,
    block_tables: jax.Array,  # replicated
    seq_lens: jax.Array,  # replicated
    k_new: jax.Array,  # [S, H_kv, d] — heads over 'tp', replicated over 'sp'
    v_new: jax.Array,
    interpret: bool = False,
    *,
    k_scales: jax.Array | None = None,  # [num_pages, P, H_kv] — P over 'sp',
    v_scales: jax.Array | None = None,  # heads over 'tp'
    scales_laid: bool = False,
) -> jax.Array:
    """Context-parallel kernel wrapper: each sp rank holds a 1/sp slice of
    every page and runs the kernel over it (pos_base = rank * P_local, so
    masking stays exact against global token positions); the unnormalized
    (acc, m, l) states then merge across the sp axis with one pmax + two
    psums of [S, H]-sized values — the online-softmax merge, never a
    gathered context. The self term folds once after the merge (replicated
    over sp). Composes with tp (heads stay head-parallel, no collectives
    on that axis). int8 pages ride along: the scale twins shard exactly
    like the pages ('sp' on rows, 'tp' on KV heads)."""
    from jax.sharding import PartitionSpec as P

    sp = _mesh_axes(mesh)[1]
    P_global = k_pages.shape[1]
    P_local = P_global // sp
    quantized = k_scales is not None

    def body(q, kp, vp, bt, sl, kn, vn, *scales):
        pos_base = (jax.lax.axis_index("sp") * P_local).reshape(1)
        acc, m, l = _paged_state(
            q, kp, vp, bt, sl, interpret,
            pos_base=pos_base, global_page_size=P_global,
            k_scales=scales[0] if scales else None,
            v_scales=scales[1] if scales else None,
            kv_heads=kn.shape[1], scales_laid=scales_laid,
        )
        m_g = jax.lax.pmax(m, "sp")
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, "sp")
        acc_g = jax.lax.psum(acc * corr[..., None], "sp")
        return _fold_self_term(q, kn, vn, acc_g, m_g, l_g)

    q_spec = P(None, "tp", None)
    pages_spec = P(None, "sp", "tp")
    new_spec = P(None, "tp", None)
    in_specs = (q_spec, pages_spec, pages_spec, P(None, None), P(None),
                new_spec, new_spec)
    operands = [q, k_pages, v_pages, block_tables, seq_lens, k_new, v_new]
    if quantized:
        scale_spec = _laid_scale_spec(sp) if scales_laid else pages_spec
        in_specs = in_specs + (scale_spec, scale_spec)
        operands += [k_scales, v_scales]
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=q_spec,
        check_vma=False,
    )(*operands)


def paged_decode_attention_cache_plus_new_sharded(
    mesh,
    q: jax.Array,
    k_pages: jax.Array,  # [num_pages, P, H_kv * d] — the row's KV heads over 'tp'
    v_pages: jax.Array,
    block_tables: jax.Array,
    seq_lens: jax.Array,
    k_new: jax.Array,  # [S, H_kv, d] — KV heads sharded over 'tp'
    v_new: jax.Array,
    interpret: bool = False,
    *,
    k_scales: jax.Array | None = None,  # [num_pages, P, H_kv] f32 — int8 pages
    v_scales: jax.Array | None = None,
    scales_laid: bool = False,
) -> jax.Array:
    from jax.sharding import PartitionSpec as P

    tp, sp = _mesh_axes(mesh)
    if tp == 1 and sp == 1:  # no mesh, or one chip: the kernel as it is
        return paged_decode_attention_cache_plus_new(
            q, k_pages, v_pages, block_tables, seq_lens, k_new, v_new, interpret,
            k_scales=k_scales, v_scales=v_scales, scales_laid=scales_laid,
        )
    if sp > 1:
        return paged_decode_attention_cache_plus_new_sp_sharded(
            mesh, q, k_pages, v_pages, block_tables, seq_lens, k_new, v_new,
            interpret, k_scales=k_scales, v_scales=v_scales, scales_laid=scales_laid,
        )
    new_spec = P(None, "tp", None)
    if k_scales is not None:
        return _shard_wrap(
            paged_decode_attention_cache_plus_new,
            mesh,
            interpret,
            extra_sharded=(new_spec, new_spec),
            with_scales=True,
            scales_laid=scales_laid,
        )(q, k_pages, v_pages, block_tables, seq_lens, k_new, v_new,
          k_scales, v_scales)
    return _shard_wrap(
        paged_decode_attention_cache_plus_new,
        mesh,
        interpret,
        extra_sharded=(new_spec, new_spec),
    )(q, k_pages, v_pages, block_tables, seq_lens, k_new, v_new)
