"""The indexer's choice of a decode lane's rows by the chip's kernel
(`ops/pallas/index_select.py`, interpreted here) against the list
`jax.lax.top_k` gives (`ops.attention.topk_rows`): the same SET on every
case, ties at the threshold to the earlier row, through the seam both sparse
families call (`ops.paged.chosen_rows`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agentcontrolplane_tpu.ops import attention, paged

PAGE, M, TOPK, WIDTH = 16, 8, 16, 128
C = M * PAGE


def lanes(case: str, seed: int = 62):
    """(ik pages, tables, seq_lens, new ik rows, qi, wi) of a case: a lane a length of interest."""
    Hi = 64 if "heads64" in case else 16
    lens = np.asarray([0, 5, TOPK - 2, TOPK - 1, TOPK, 100, C - 1], np.int32)  # seq_len + 1 below, at and above topk
    S = len(lens)
    shapes = ((1 + S * M, PAGE, WIDTH), (S, Hi, WIDTH), (S, Hi), (S, WIDTH))
    pages, qi, wi, new = (np.array(jax.random.normal(jax.random.fold_in(jax.random.key(seed), i), shape, jnp.float32))
                          for i, shape in enumerate(shapes))
    if "ties" in case:  # whole runs of equal rows: a lane's rows are copies of three, so two thirds of them tie at any threshold
        pages = np.repeat(pages[:, :1, :], PAGE, axis=1)
        pages[1:] = pages[1 + np.arange(S * M) % 3]
    if "new-row" in case:  # the new token's row scores over (under) every cached one: its key is every head's query, signed
        wi = np.abs(wi)
        new = qi.sum(1) * (50.0 if "new-row-chosen" in case else -50.0)
    tables = (1 + np.arange(S * M, dtype=np.int32)).reshape(S, M)
    dt = jnp.bfloat16 if "bf16" in case else jnp.float32
    return tuple(jnp.asarray(a, dt if a.dtype == np.float32 else None) for a in (pages, tables, lens, new, qi, wi))


def reference(ik_pages, tables, seq_lens, new_ik, qi, wi):
    """The parent's form: the scores with the new row's in its place, `jax.lax.top_k`'s list, and by numpy which lanes tied."""
    S = tables.shape[0]
    pos = jnp.arange(C, dtype=jnp.int32)
    cached = attention.index_scores(qi[:, None], wi[:, None], ik_pages[tables].reshape(S, C, -1))[:, 0]
    own = attention.index_scores(qi[:, None], wi[:, None], new_ik[:, None])[:, 0]
    scores = jnp.where(pos[None] == seq_lens[:, None], own, cached)
    columns, chosen = attention.topk_rows(scores, pos[None] <= seq_lens[:, None], TOPK)
    sets, tied = [], []
    for b in range(S):
        n = int(seq_lens[b])
        row, mine = np.asarray(scores[b, : n + 1]), sorted(np.asarray(columns[b])[np.asarray(chosen[b])].tolist())
        sets.append(mine)
        tied.append(bool((row >= row[mine].min()).sum() > len(mine)))
    return sets, tied


CASES = ["heads16", "heads64", "heads16-bf16", "heads16-ties", "heads64-ties-bf16", "heads16-new-row-chosen", "heads16-new-row-left-out",
         "heads16-given"]


@pytest.mark.parametrize("case", CASES)
def test_the_kernels_set_is_top_ks_set_on_every_lane(case, monkeypatch):
    """Lanes of 0, 5, 14, 15, 16, 100 and 127 cached rows in one call
    (`seq_len + 1` below, at and above `topk` = 16; a lane of length 0
    chooses its own row alone): the kernel's positions, ascending, are the
    set `topk_rows` chooses by score, bit for bit the same scores on both
    sides (`index_scores` makes them for both); the list is padded with
    position 0 and masked past a lane's rows; `tied` says which lanes' tie
    rule engaged (none on random scores; the long lanes where whole runs of
    rows are equal, and there the EARLIER rows win, as `top_k` breaks
    ties); the new token's row is chosen when it scores highest and
    left out when lowest; and a choice handed in (`given`) never reaches the
    kernel."""
    args = lanes(case)
    seq_lens = np.asarray(args[2])
    if case.endswith("given"):
        from agentcontrolplane_tpu.ops.pallas import index_select

        monkeypatch.setattr(index_select, "index_select", lambda *a, **kw: pytest.fail("the kernel ran on a choice handed in"))
        given = jnp.where(jnp.arange(TOPK)[None] <= jnp.minimum(seq_lens, 3)[:, None], jnp.arange(TOPK)[None], -1).astype(jnp.int32)
        pos, chosen, tied = paged.chosen_rows(args[0], PAGE, *args[1:], TOPK, given, interpret=True)
        assert np.array_equal(np.where(chosen, pos, -1), given) and not np.asarray(tied).any()
        return
    want, want_tied = reference(*args)
    pos, chosen, tied = (np.asarray(a) for a in paged.chosen_rows(args[0], PAGE, *args[1:], TOPK, interpret=True))
    assert pos.shape == chosen.shape == (len(seq_lens), TOPK) and pos.dtype == np.int32
    for b, n in enumerate(seq_lens):
        assert chosen[b].sum() == min(TOPK, n + 1) and not pos[b][~chosen[b]].any()
        assert pos[b][chosen[b]].tolist() == want[b], (case, b, n)
    assert tied.tolist() == want_tied
    over = seq_lens + 1 > TOPK
    if "ties" in case:
        assert tied[seq_lens >= 100].all() and not tied[~over].any()  # a run of ~33 equal rows holds the sixteenth
    else:
        assert not tied.any()
    if "new-row" in case:  # the new token's row stands at column `seq_len`
        assert [int(n) in want[b] for b, n in enumerate(seq_lens)] == ([True] * len(seq_lens) if "chosen" in case else (~over).tolist())
    # off the TPU the seam takes `top_k`'s list: the same sets and the same tied lanes
    pos2, chosen2, tied2 = (np.asarray(a) for a in paged.chosen_rows(args[0], PAGE, *args[1:], TOPK))
    assert [sorted(p[c].tolist()) for p, c in zip(pos2, chosen2)] == want and tied2.tolist() == want_tied


@pytest.mark.parametrize("S, C, topk", [(16, 6144, 256), (3, 200, 300), (5, 2048, 2048)], ids=["groups-of-8", "fewer-columns-than-topk", "all"])
def test_the_kernel_alone_over_lane_groups_and_column_tiles(S, C, topk):
    """`index_select` by itself: sixteen lanes in two groups of eight over
    columns that are whole tiles; columns that are no whole tile and fewer
    than `topk` (padded with `-inf`, the list as long as the columns); and
    `topk` the whole width. Scores coarse enough to tie everywhere."""
    from agentcontrolplane_tpu.ops.pallas.index_select import index_select

    scores = jnp.round(jax.random.normal(jax.random.key(S), (S, C), jnp.float32) * 2) / 2
    lens = jnp.asarray(np.linspace(0, C - 1, S).astype(np.int32))
    valid = jnp.arange(C)[None] <= lens[:, None]
    k = min(topk, C)
    want = jnp.minimum(k, lens + 1)
    got, tied = index_select(jnp.where(valid, scores, -jnp.inf), want, k, interpret=True)
    columns, chosen = attention.topk_rows(scores, valid, topk)
    assert got.shape == (S, k)
    for b in range(S):
        assert np.asarray(got[b, : int(want[b])]).tolist() == sorted(np.asarray(columns[b])[np.asarray(chosen[b])].tolist())
    assert bool(tied[-1]) == (C > k)


def test_lanes_tied_reads_through_both_families_counters():
    """`lanes_tied` is the sixth of the `sparse` counters a decode row keeps
    (after `lanes_past_topk`), in `models/keye.py` and `models/dots.py`
    alike; a prefill row reads 0."""
    from agentcontrolplane_tpu.models import dots, keye
    from agentcontrolplane_tpu.ops.moe import COUNTS_HEAD

    for family, preset in ((keye, "keye-tiny"), (dots, "dots-tiny")):
        c = family.PRESETS[preset]
        cut = 1 + COUNTS_HEAD + len(c.held)
        width = np.asarray(jax.eval_shape(lambda: family.init_paged_cache(c, 4, PAGE, max_slots=2))["state"]["counts"].shape)
        total = np.zeros(width, np.int64)
        total[0, cut + 4], total[0, cut + 5] = 7, 3
        got = family.describe_counters(c, total)["sparse"]
        assert (got["decode"]["lanes_past_topk"], got["decode"]["lanes_tied"]) == (7, 3)
        assert got["prefill"]["lanes_tied"] == 0 and family.describe_counters(c, None)["sparse"]["decode"]["lanes_tied"] == 0


@pytest.mark.parametrize("coarse", [False, True], ids=["random", "ties"])
def test_the_chips_parity_case_passes_interpreted(coarse):
    """`engine/kernel_parity.py index_select_parity`, what
    `tests/engine/test_tpu_hardware.py` runs compiled on a chip, at a small
    size through the interpreter: lanes of 0 rows, half of `topk` and the
    whole width among random ones."""
    from agentcontrolplane_tpu.engine.kernel_parity import index_select_parity

    got = index_select_parity(9, lanes=8, columns=4096, topk=256, coarse=coarse, interpret=True)
    assert got["ok"] and got["seq_lens"][:3] == [0, 128, 4095], got
    assert (got["lanes_tied"] >= 6) if coarse else got["lanes_tied"] == 0, got
