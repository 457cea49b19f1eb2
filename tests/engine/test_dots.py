"""dots3-note-prev's language model through the normal path, the PROGRAMS
through the caches: the program against the plain reference
(acpbench/families/dots_reference.py, which imports nothing of the program)
for `forward`, prefill, continuation and decode through the pool of three
leaves (latent rows and indexer keys on the page list, a ring of latent rows a
slot), with the choice of rows and experts free and with it given; a context
that crosses `topk` and the window's edge inside one run of decode steps; the
blocked continuation against the plain one; every cache control seen by the
comparison, and a fault planted in the program's indexer refused. The
operators and the reference's controls: `test_dots_operators.py` (apart, so
that each file stays under the 300 s a file of ROADMAP's tier-1 budget). The
engine serving it: `test_dots_engine.py`.

CPU, tiny sizes (a dense full layer, an expert full layer, three sliding
layers; 4 heads over a latent of 24, a window of 9 rows at 2 heads over a
latent of 40, an indexer of 4 heads of 16 that chooses 8 rows, 16 experts
top-2 of which 2 held), float32, seeded weights.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from acpbench import check
from acpbench.families import dots_reference
from agentcontrolplane_tpu.models import dots, keye
from agentcontrolplane_tpu.ops import attention, paged

from ._dots_cases import FILE, PAGE, built, lanes, prefilled, sizes, text_tokens

# -- the program against the plain reference ---------------------------------------------------------


def test_forward_agrees_with_the_plain_reference_free_and_given():
    """Free, both sides choose the same rows and experts (float32: nothing
    for rounding to decide) and the logits agree; given the program's
    choices, the reference gives the same logits again, and given OTHER rows
    (the most recent 8) it does not: `select=` is read, by both."""
    family, pc, mesh, params = built()
    tokens, rows = text_tokens()
    got, (chose, routed) = dots.forward(params, jnp.asarray(tokens), pc, tell=True)
    want = dots_reference.logits(params, sizes(), tokens, rows)
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5)
    assert chose.shape == (pc.n_full, 2, 40, 5) and routed.shape == (pc.n_layers - pc.first_dense, 2, 40, 2)
    free = dots_reference.choices(params, sizes(), tokens, against=chose)
    assert np.array_equal(free["select"], chose) and np.array_equal(free["route"], routed)
    t = np.arange(tokens.shape[1])
    assert np.array_equal(np.asarray(free["both"])[0, 0], np.minimum(t + 1, pc.index_topk))
    assert float(jnp.max(free["missed_weight"])) == 0.0
    given = dots_reference.logits(params, sizes(), tokens, rows, select=chose, route=routed)
    np.testing.assert_allclose(got, given, atol=5e-5, rtol=5e-5)
    recent = jnp.packbits((t[None, :] <= t[:, None]) & (t[None, :] > t[:, None] - pc.index_topk), axis=-1, bitorder="little")
    other = jnp.broadcast_to(recent, chose.shape)
    moved = dots_reference.logits(params, sizes(), tokens, rows, select=other, route=routed)
    assert float(jnp.abs(moved - want).max()) > 0.05
    np.testing.assert_allclose(dots.forward(params, jnp.asarray(tokens), pc, select=other, route=routed), moved,
                               atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("seed", [11, 2**31 + 7], ids=["seed-11", "seed-over-31-bits"])
def test_program_agrees_with_the_plain_reference_through_the_pool_and_the_rings(seed):
    """The family's cache check as every run of the cell makes it: the
    prompt's prefill and 8 decode steps through the pool's leaves and the
    slots' rings, free and telling; the longer prefills given; the reference
    given the same (prompts of 24-56 choose 8 rows of theirs and see 9, page
    8: the rings of 3 pages have wrapped). The choices agree with the free
    float32 reference's to the row."""
    family, pc, mesh, params = built(seed)
    s = check.sample(FILE["check"], FILE["vocab_size"], PAGE, seed)
    pre, dec, chosen = family.cache_readings(FILE, pc, params, mesh, s, False)
    want = check.reference_logits(functools.partial(family.reference_logits, FILE, params), s)
    numbers = check.compare((pre, dec), want)
    assert numbers["finite"] and numbers["top1_agree"] == 1.0
    assert numbers["prefill_rel_rms"] < 3e-5 and numbers["decode_rel_rms"] < 3e-5, numbers
    assert set(chosen.values()) == {0.0} and "select_cache_miss" in chosen

@pytest.mark.parametrize("interpret", [False, True], ids=["top_k", "kernel"])
def test_decode_steps_cross_topk_and_the_windows_edge_in_one_run_and_the_counters_count(interpret):
    """One decode program either side of `topk` (8 rows) and of the window
    (9 rows): a lane of 5 cached rows walks to 13 beside lanes of 20 and 33,
    every step against `forward` at its own length; the short lane's list is
    padded and masked until it holds 8 rows, and its ring holds fewer rows
    than the window until it has 9; and what the counters count. With the
    choice `jax.lax.top_k`'s (what a CPU serves) and the chip's kernel's,
    interpreted (PR 62): the same numbers, `lanes_tied` among them."""
    family, pc, mesh, params = built()
    tokens, _ = text_tokens(B=3, T=48, seed=2)
    full = dots.forward(params, jnp.asarray(tokens), pc)
    cache, tables, lengths, logits = prefilled(pc, params, tokens, [5, 20, 33])
    np.testing.assert_allclose(logits, full[jnp.arange(3), lengths - 1], atol=5e-5, rtol=5e-5)
    live = []
    step = jax.jit(lambda ca, tok, n: dots.decode_step_paged(params, ca, tok, n, tables, jnp.ones((3,), bool), pc, tell=True,
                                                             interpret=interpret))
    for j in range(8):
        cache, logits, (rows, experts) = step(cache, jnp.asarray(tokens)[jnp.arange(3), lengths + j], lengths + j)
        np.testing.assert_allclose(logits, full[jnp.arange(3), lengths + j], atol=5e-5, rtol=5e-5)
        rows = np.asarray(rows)  # [full-type layers, lanes, topk]
        assert rows.shape == (pc.n_full, 3, pc.index_topk) and experts.shape == (4, 3, 1, 2)
        assert ((rows[:, 0] >= 0).sum(-1) == min(6 + j, 8)).all() and ((rows[:, 1:] >= 0).sum(-1) == 8).all()
        live.append([6 + j, 21 + j, 34 + j])
    got = dots.describe_counters(pc, np.asarray(dots.counters(cache)))
    sparse, window = got["sparse"], got["window"]
    seen = np.asarray(live)
    assert sparse["decode"] == {"steps": 8, "rows_scored": 8 * 3 * 8 * PAGE * 2, "rows_chosen": int(np.minimum(seen, 8).sum()) * 2,
                                "rows_dense": int(seen.sum()) * 2, "lanes_past_topk": int((seen > 8).sum()), "lanes_tied": 0}
    assert window["decode"] == {"steps": 8, "rows_read": int(np.minimum(seen, 9).sum()), "rows_unwindowed": int(seen.sum()),
                                "slots_past_window": int((seen > 9).sum())}
    assert sparse["prefill"]["rows_dense"] == sum(n * (n + 1) // 2 for n in (5, 20, 33)) * 2
    assert (sparse["topk"], sparse["layers"], sparse["row_values"], sparse["ik_row_bytes_stored"]) == (8, 2, 32, 16 * 4)
    assert (window["window"], window["window_layers"], window["full_layers"], window["row_values"]) == (9, 3, 2, 48)
    # the three leaves: the full layers' latent row and key on the page list, the sliding layers' row in rings
    ring = paged.ring_size(pc.window, PAGE)
    assert (pc.window, ring) == (16, 3)
    assert cache["kv"].shape == (2, 25, PAGE, 128) and cache["ik"].shape == (2, 25, PAGE, 16)
    assert cache["wkv"].shape == (3, 4 * ring, PAGE, 128)
    assert not np.asarray(cache["kv"])[..., 32:].any() and not np.asarray(cache["wkv"])[..., 48:].any()  # stored on whole tiles

@pytest.mark.parametrize("block", [8, 16], ids=["blocks-of-8", "blocks-of-16"])
def test_a_continuation_in_blocks_of_query_rows_is_the_whole_one_and_reads_rows_it_did_not_write(monkeypatch, block):
    """A prompt prefilled to a page-aligned cut and continued (the engine's
    chunks and a resumed request's tail) gives the whole prompt's logits,
    with the continuation's rows taken `CONTINUE_BLOCK` at a time (the mask a
    block at a time, a group of heads expanded and attended a block at a
    time; a sliding layer over its ring and the band of its own keys) and
    with the keys folded `KEY_BLOCK` at a time; the decode step after it
    reads both parts' rows through pages and ring."""
    family, pc, mesh, params = built()
    monkeypatch.setattr(dots, "CONTINUE_BLOCK", block)
    monkeypatch.setattr(attention, "KEY_BLOCK", 32)
    tokens, _ = text_tokens(B=2, T=64, seed=4)
    full = dots.forward(params, jnp.asarray(tokens), pc)
    cut, lengths = jnp.asarray([16, 24], jnp.int32), jnp.asarray([47, 52], jnp.int32)
    cache, tables, _, _ = prefilled(pc, params, tokens, cut, M=8, T=32)
    T = 32
    rest = lengths - cut
    chunk = jnp.stack([jnp.where(jnp.arange(T) < rest[b], jnp.roll(jnp.asarray(tokens)[b], -int(cut[b]))[:T], 0) for b in range(2)])
    ids = jnp.stack([jnp.where(jnp.arange(T // PAGE) < -(-rest[b] // PAGE),
                               jnp.roll(tables[b], -int(cut[b]) // PAGE)[: T // PAGE], 0) for b in range(2)])
    cache, logits = dots.prefill_paged_continue(params, cache, chunk, rest, cut, ids, tables, lanes(2), pc)
    np.testing.assert_allclose(logits, full[jnp.arange(2), lengths - 1], atol=5e-5, rtol=5e-5)
    cache, logits = dots.decode_step_paged(params, cache, jnp.asarray(tokens)[jnp.arange(2), lengths], lengths, tables,
                                           jnp.ones((2,), bool), pc)
    np.testing.assert_allclose(logits, full[jnp.arange(2), lengths], atol=5e-5, rtol=5e-5)

# -- the cache's controls --------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def cache_reading(seed, **control):
    """The cache check's numbers under a control of the cache (none: the sound program's, read once a seed)."""
    family, pc, mesh, params = built()
    s = check.sample(FILE["check"], FILE["vocab_size"], PAGE, seed)
    pre, dec, chosen = family.cache_readings(FILE, pc, params, mesh, s, False, **control)
    want = family.reference_logits(FILE, params, s["tokens"], s["rows"])  # given what that program chose
    return {**check.compare((pre, dec), want), **chosen}


@pytest.mark.parametrize("control,number,least,seed", [
    # keys of 16 values rounded to int8 move one choice in two hundred here, and the sample of seed 3 holds none
    ({"ik_int8": True}, "select_cache_miss_all", 1e-3, 4), ({"kv_int8": True}, "cache_excess", 0.5, 3),
    ({"wkv_int8": True}, "cache_excess", 0.5, 3), ({"ring_short": 1}, "cache_excess", 0.5, 3),
    ({"ik_crossed": True}, "select_cache_miss", 0.3, 3)])
def test_each_cache_control_is_seen(control, number, least, seed):
    """int8 indexer keys and another request's keys choose other rows in the
    decode steps (the logits, compared with the choice given, do not see it:
    the choices' own numbers do); int8 latent rows, of the page list or of
    the rings, and a ring one row short show in the cache's excess."""
    sound, seen = cache_reading(seed), cache_reading(seed, **control)
    assert seen[number] > least > abs(sound[number]), (sound[number], seen[number])


def test_past_a_limit_on_the_choices_the_decode_logits_are_not_numbers(capsys):
    family, pc, mesh, params = built()
    s = check.sample(FILE["check"], FILE["vocab_size"], PAGE, 4)
    strict = dict(FILE, check=dict(FILE["check"], select_limits={"select_cache_miss_all": 0.001}))
    _pre, dec = family.cached_logits(strict, pc, params, mesh, s, False, ik_int8=True)
    assert not np.isfinite(np.asarray(dec)).any() and "select_cache_miss_all=" in capsys.readouterr().out
    _pre, dec = family.cached_logits(FILE, pc, params, mesh, s, False)
    assert np.isfinite(np.asarray(dec)).all()


@pytest.mark.parametrize("fault,number", [
    ("recent", "select_miss_prefill"), ("topk_half", "missed_weight"), ("index_rope_off", "select_miss_decode"),
    ("index_norm_off", "select_miss_decode")])
def test_a_fault_planted_in_the_programs_indexer_is_refused_by_the_choices_numbers(fault, number, capsys):
    """The program traced with one of the reference's faults of the choice
    in its indexer (`dots._planted`): the number named lies past its limit,
    `cached_logits` hands back decode logits that are not numbers, and the
    program's modules are their own again after."""
    family, pc, mesh, params = built()
    s = check.sample(FILE["check"], FILE["vocab_size"], PAGE, 3)
    before = (dots._rope_first, dots._layer_norm, keye.topk_rows_mask, attention.topk_rows, attention.topk_rows_mask)
    pre, dec = family.cached_logits(FILE, pc, params, mesh, s, False, indexer=fault)
    assert before == (dots._rope_first, dots._layer_norm, keye.topk_rows_mask, attention.topk_rows, attention.topk_rows_mask)
    assert not np.isfinite(np.asarray(dec)).any() and np.isfinite(np.asarray(pre)).all()
    assert f"[check] {number}=" in (out := capsys.readouterr().out)
    line = next(ln for ln in out.splitlines() if ln.startswith(f"[check] {number}="))
    assert line.endswith("EXCEEDED"), out
    with pytest.raises(ValueError, match="no fault 'rope_twice'"):
        family.cache_readings(FILE, pc, None, None, {}, False, indexer="rope_twice")
