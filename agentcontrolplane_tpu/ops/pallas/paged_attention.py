"""Pallas TPU kernel: paged decode attention.

One new token per slot attends over its page list. The kernel walks each
sequence's block table (scalar-prefetched so page indices are known before
the body runs), DMAs K/V pages HBM -> VMEM with double buffering, and
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

Grid: one program per slot. A turn of the walk covers G pages, G chosen
so that a turn is one 128-lane tile of tokens (``pages_per_turn``: 8 at the
engine's page 16, 1 at page 128): the turn's pages are DMA'd each into its
own row window of one ``[G * P, H_kv * d]`` buffer, and the body runs one
pair of products per KV head over all of them. The walk is bound by what a
turn costs (DMA waits, the chained softmax update, MXU fill and drain), not
by bytes, so fewer, fuller turns are the lever. Per-program working set is
NBUF x 2 (K+V) x [G * P, H_kv * d] — 1 MB in VMEM for Qwen2.5-7B geometry
(page 16, 4 KV heads, d 128, bf16).

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
fetch also DMAs its scale row on dedicated semaphore lanes. The per-row
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

Tested in interpreter mode on CPU against the exact reference
(tests/engine/test_paged*.py), compiled for a described v5e
(tests/engine/test_chip_compile.py), and run compiled on the chip against
the reference (chip_smoke.py, tests/engine/test_tpu_hardware.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
NBUF = 4  # DMA pipeline depth: NBUF-1 turns' fetches kept in flight per walk
LANES = 128  # a turn of the walk covers one lane tile of tokens
# scratch the walk may claim of the 16 MiB scoped VMEM a kernel gets by
# default; the rest is the body's f32 windows and the pipelined q / outputs
_SCRATCH_BUDGET = 8 << 20
# In-kernel products run at f32 contract precision: the walk is the same
# f32 math as the XLA reference, not a bf16-pass approximation of it. The
# one exception is exact: q . k with both sides bf16 (see the KV-head loop).
_F32 = jax.lax.Precision.HIGHEST


def pages_per_turn(P_local: int, dtype, H_kv: int, d: int, quantized: bool = False) -> int:
    """G, the pages one turn of the walk fetches and folds: as many as make
    a turn one lane tile of tokens, from what the kernel can see alone.

    G = 1 (a page a turn, each page DMA'd into a whole buffer) where a page
    cannot land on a whole-tile row window of a shared buffer — its rows
    must be a multiple of the dtype's sublane tile: 8 for f32, 16 for bf16,
    32 for int8 — and for int8 pages at any size: their scale rows are laid
    out head-major per page and do not follow a G-page turn. G halves until
    the K and V scratch fits ``_SCRATCH_BUDGET``.
    """
    itemsize = jnp.dtype(dtype).itemsize
    G = max(1, LANES // P_local)
    if quantized or P_local % (32 // itemsize):
        G = 1
    while G > 1 and 2 * NBUF * G * P_local * H_kv * d * itemsize > _SCRATCH_BUDGET:
        G //= 2
    return G


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
    q_ref,  # [1, H_kv, n_rep, d] (VMEM) — this program's slot, grouped by KV head
    k_pages_ref,  # [num_pages, P_local, H_kv * d] (HBM/ANY)
    v_pages_ref,  # [num_pages, P_local, H_kv * d]
    # quantized=True only: ks_pages_ref / vs_pages_ref
    #   [num_pages, 1, SC] f32 (HBM/ANY) — a page's per-row-per-head
    #   scales, head-major ([H_kv, P_local] flattened), lane-padded to SC
    # outputs, grouped by KV head like q:
    # acc_ref: [1, H_kv, n_rep, d] f32 — unnormalized weighted V sum
    # m_ref:   [1, H_kv, n_rep, 1] f32 — running max
    # l_ref:   [1, H_kv, n_rep, 1] f32 — running denominator
    # scratch
    # k_buf / v_buf: [NBUF, G * P_local, H_kv * d] (VMEM) — a turn's G pages
    # quantized=True only (G == 1): ks_buf / vs_buf [NBUF, 1, SC] f32 (VMEM)
    # sems: DMA sems [NBUF, 4 if quantized else 2]
    *rest,
    page_size: int,  # GLOBAL page size (pages hold this many tokens)
    quantized: bool = False,
    head_dim: int | None = None,  # the model's, where heads share a lane window
    starts_ref=None,  # [S] int32 (SMEM): a slot's first valid row (the window walk)
    ring: int = 0,  # > 0: the table is a ring, page a of the sequence at a % ring
):
    # int8 walk (quantized=True): pages hold int8 values plus f32 scale
    # twins (one scale per row per KV head). The fetch loop DMAs each
    # page's scale row alongside it on its own semaphore lanes and the body
    # applies the scales in VMEM (see the KV-head loop), so int8 decode
    # takes the kernel path with the same (acc, m, l) contract as the f32
    # walk.
    if quantized:
        (ks_pages_ref, vs_pages_ref, acc_ref, m_ref, l_ref,
         k_buf, v_buf, ks_buf, vs_buf, sems) = rest
    else:
        acc_ref, m_ref, l_ref, k_buf, v_buf, sems = rest
        ks_pages_ref = vs_pages_ref = ks_buf = vs_buf = None
    # Under context-parallel serving each rank holds a [P_local = P/sp]
    # slice of every page (pos_base = rank * P_local); the walk length and
    # token positions are computed with the GLOBAL page size so masking is
    # exact, while DMAs and compute touch only the local slice. sp=1 runs
    # with pos_base=0 and P_local == page_size (the original behavior).
    s = pl.program_id(0)
    seq_len = seq_lens_ref[s]
    n_pages = jax.lax.div(seq_len + page_size - 1, page_size)
    # The window walk (``starts_ref``): rows before a slot's first valid row
    # are not the query's to see. The walk begins at the page that holds that
    # row, skips every page before it, and masks the rows of that first page
    # that lie before the edge; ``first_page`` and ``n_pages`` are then of the
    # walk and not of the sequence. With ``ring`` the table has ``ring``
    # entries a slot and page ``a`` of the sequence sits at ``a % ring``: a
    # window of at most ``(ring - 1)`` pages of rows touches no entry twice.
    if starts_ref is not None:
        first_row = starts_ref[s]
        first_page = jax.lax.div(first_row, page_size)
        n_pages = jnp.maximum(n_pages - first_page, 0)
    _, n_kv_heads, n_rep, d = q_ref.shape
    P = k_pages_ref.shape[1]  # local slice length
    pos_base = pos_base_ref[0]
    NBUF, T = k_buf.shape[:2]  # T = G * P tokens a turn
    G = T // P
    n_turns = jax.lax.div(n_pages + G - 1, G)

    scale = 1.0 / ((head_dim or d) ** 0.5)
    # q . k in one bf16 MXU pass where both sides are bf16 (int8 widens to
    # bf16 exactly): bf16 x bf16 products are exact in the f32 accumulator,
    # so with 1/sqrt(d) applied to the f32 logits this is the f32 product.
    # f32 q or pages take the f32 contract with q pre-scaled once.
    one_pass = q_ref.dtype == jnp.bfloat16 and k_buf.dtype in (jnp.bfloat16, jnp.int8)
    if one_pass:
        qs = [q_ref[0, h] for h in range(n_kv_heads)]
    else:
        qs = [q_ref[0, h].astype(jnp.float32) * scale for h in range(n_kv_heads)]

    def fetch(t, slot, act):  # act: "start" or "wait", every DMA of turn t
        # a turn's DMAs: page t*G+g of the block table into rows
        # [g*P, (g+1)*P) of the turn's buffer. The caller holds t < n_turns,
        # so the turn's first page is live; the rest are guarded one by one
        # (G = 1: one unguarded page into the whole buffer).
        for g in range(G):
            def run(g=g):
                at = t * G + g
                if starts_ref is not None:
                    at = first_page + at
                page = block_tables_ref[s, jax.lax.rem(at, ring) if ring else at]
                rows = pl.ds(g * P, P)
                copies = [
                    (k_pages_ref.at[page], k_buf.at[slot, rows]),
                    (v_pages_ref.at[page], v_buf.at[slot, rows]),
                ]
                if quantized:
                    copies += [
                        (ks_pages_ref.at[page], ks_buf.at[slot]),
                        (vs_pages_ref.at[page], vs_buf.at[slot]),
                    ]
                for i, (src, dst) in enumerate(copies):
                    getattr(pltpu.make_async_copy(src, dst, sems.at[slot, i]), act)()

            if g == 0:
                run()
            else:
                pl.when(t * G + g < n_pages)(run)

    if G > 1:
        # Rows of a turn's buffer that no DMA wrote (the pages past n_pages
        # in the walk's last turn) carry weight 0 into p . v, and 0 x NaN is
        # NaN: start every walk from a zeroed V scratch. A buffer's later
        # turns leave only fetched, finite rows behind. K needs none of
        # this: its logits are replaced by the mask, not multiplied.
        v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)

    # page walks are small-transfer latency-bound: keep NBUF-1 turns'
    # fetches in flight (ramp turns 0..NBUF-2 here, steady state issues
    # t+NBUF-1)
    def ramp(t, _):
        @pl.when(t < n_turns)
        def _():
            fetch(t, t, "start")
        return 0

    jax.lax.fori_loop(0, NBUF - 1, ramp, 0)

    # token position of a turn's column c, less the turn's first: row c % P
    # of the turn's page c // P, pages page_size tokens apart (sp=1: c)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    col = lane
    if page_size != P:
        for g in range(1, G):
            col = col + jnp.where(lane >= g * P, page_size - P, 0)

    def body(t, carry):
        slot = jax.lax.rem(t, NBUF)
        # issue the deepest prefetch; its buffer was consumed at t-1
        nxt = t + NBUF - 1

        @pl.when(nxt < n_turns)
        def _():
            fetch(nxt, jax.lax.rem(nxt, NBUF), "start")

        fetch(t, slot, "wait")
        pos = t * (G * page_size) + pos_base + col
        if starts_ref is not None:
            pos = pos + first_page * page_size
        valid = pos < seq_len  # [1, T]
        if starts_ref is not None:
            valid = valid & (pos >= first_row)
        if quantized:
            ks = ks_buf[slot]  # [1, >= H_kv * P], head-major
            vs = vs_buf[slot]
        out = []
        # Static loop over the KV heads. Each takes its lane-aligned [T, d]
        # column window of the turn's buffer (d % 128 == 0) and two plain
        # 2-D products with its [n_rep, d] query group — the only shapes in
        # the body are 2-D, which is what Mosaic lays out (a 4-D grouped
        # reshape of the logits is refused: "unsupported shape cast").
        for h in range(n_kv_heads):
            m, l, acc = carry[h]  # [n_rep,1], [n_rep,1], [n_rep,d]
            k = k_buf[slot, :, h * d:(h + 1) * d]  # [T, d]
            v = v_buf[slot, :, h * d:(h + 1) * d].astype(jnp.float32)
            if one_pass:
                logits = jax.lax.dot_general(
                    qs[h], k.astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale  # [n_rep, T]
            else:
                logits = jax.lax.dot_general(
                    qs[h], k.astype(jnp.float32), (((1,), (1,)), ((), ())),
                    precision=_F32, preferred_element_type=jnp.float32,
                )
            if quantized:
                # the per-row scale factors out of both products: scale the
                # [n_rep, P] logits and weights by this head's [1, P] scale
                # row, never the [P, d] page. Masked rows (stale scales
                # incl. TRASH_PAGE) stay finite, so the pos mask zeroes
                # their weight as in f32.
                logits = logits * ks[:, h * P:(h + 1) * P]
            logits = jnp.where(valid, logits, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(logits, axis=1, keepdims=True))
            p = jnp.exp(logits - m_new)  # [n_rep, T]
            correction = jnp.exp(m - m_new)  # [n_rep, 1]
            l = l * correction + jnp.sum(p, axis=1, keepdims=True)
            pw = p * vs[:, h * P:(h + 1) * P] if quantized else p
            pv = jnp.dot(
                pw, v, precision=_F32, preferred_element_type=jnp.float32
            )  # [n_rep, d]
            out.append((m_new, l, acc * correction + pv))
        return tuple(out)

    init = tuple(
        (
            jnp.full((n_rep, 1), NEG_INF, dtype=jnp.float32),
            jnp.zeros((n_rep, 1), dtype=jnp.float32),
            jnp.zeros((n_rep, d), dtype=jnp.float32),
        )
        for _ in range(n_kv_heads)
    )
    state = jax.lax.fori_loop(0, n_turns, body, init)
    for h, (m, l, acc) in enumerate(state):
        acc_ref[0, h] = acc
        m_ref[0, h] = m
        l_ref[0, h] = l


def _window_kernel(block_tables_ref, seq_lens_ref, pos_base_ref, starts_ref, *rest, **kw):
    """``_kernel`` with a fourth prefetched scalar row: each slot's first
    valid row."""
    _kernel(block_tables_ref, seq_lens_ref, pos_base_ref, *rest, starts_ref=starts_ref, **kw)


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
    starts: jax.Array | None = None,  # [S] int32: the window walk's first valid row a slot
    ring: int = 0,  # with `starts`: the table is a ring of this many pages a slot
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Run the kernel -> unnormalized (acc [S,H,d] f32, m [S,H], l [S,H]).

    With ``starts`` it is the window walk (named ``paged_window_walk``): a
    slot's rows ``starts[s] .. seq_lens[s] - 1`` and no others, the pages
    before the first skipped and not read.

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
            head_dim=d, starts=starts, ring=ring,
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
    )

    def per_slot(*tail):
        # one slot per program; trailing dims are whole, so they tile
        return pl.BlockSpec(
            (1, H_kv, n_rep) + tail, lambda s, *_: (s, 0, 0, 0),
            memory_space=pltpu.VMEM,
        )

    in_specs = [
        per_slot(d),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    G = pages_per_turn(P, k_pages.dtype, H_kv, d, quantized)
    scratch_shapes = [
        pltpu.VMEM((NBUF, G * P, H_kv * d), k_pages.dtype),
        pltpu.VMEM((NBUF, G * P, H_kv * d), v_pages.dtype),
    ]
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
        scratch_shapes += [
            pltpu.VMEM((NBUF, 1, SC), jnp.float32),
            pltpu.VMEM((NBUF, 1, SC), jnp.float32),
        ]
        operands += [k_scales, v_scales]
    scratch_shapes.append(pltpu.SemaphoreType.DMA((NBUF, 4 if quantized else 2)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 if windowed else 3,
        grid=(S,),
        in_specs=in_specs,
        out_specs=[per_slot(d), per_slot(1), per_slot(1)],
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
