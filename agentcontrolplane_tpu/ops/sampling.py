"""Token sampling: greedy / temperature / top-k / top-p, batched per slot.

Per-slot parameters (each sequence in the continuous batch can carry its own
LLM object's sampling config, reference ``llm_types.go:41-71``): temperature
== 0 means greedy. All math in float32.

TPU note: the textbook top-k/top-p implementation sorts the [S, V] logits
twice per step — two bitonic sorts over the vocab dominate the whole
sampler (~4ms/step at [64, 32k] on v5e, comparable to a bench-1b layer
stack). Both masks only need a *threshold*, so we binary-search the
threshold value instead: ~32 fused compare+reduce passes, an order of
magnitude cheaper, and exact up to float bisection (ties at the boundary
are all kept — the sort-based variant kept an arbitrary subset of ties).

Each search runs only when a live lane of the batch asks for it
(``masks_wanted``): at a 152k vocabulary the 64 passes and their ~200 kernel
launches cost a decode step more than the draw itself, and at tp > 1,
where the logits are sharded over the vocabulary, each is an all-reduce
that waits for the one before (PERF.md, PR 39).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30
_BISECT_ITERS = 32


def _topk_threshold(logits: jax.Array, k: jax.Array, turns=_BISECT_ITERS) -> jax.Array:
    """Per-row value t such that count(logits >= t) >= k and masking
    logits < t keeps the k largest (plus boundary ties). k >= V keeps all.
    [S, V], [S] -> [S, 1]. After no turn t is the row's minimum, which
    masks nothing. Also the threshold of ``ops.attention.topk_rows_mask``
    (sparse attention's prefill: each query's ``topk``-th largest index
    score among 12k-26k, ``models/keye.py``), which snaps it to a score and
    breaks ties itself: a change here is held to both callers' tests."""
    lo = jnp.min(logits, axis=-1)  # threshold below lowest keeps everything
    hi = jnp.max(logits, axis=-1)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        count = jnp.sum(logits >= mid[:, None], axis=-1)
        ok = count >= k  # mid keeps enough -> can raise the floor
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo, hi = jax.lax.fori_loop(0, turns, body, (lo, hi))
    return lo[:, None]


def _topp_threshold(
    logits: jax.Array, top_p: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Returns (prob threshold t [S, 1], probs [S, V]): keeping probs >= t
    keeps exactly the nucleus — every token whose strictly-greater-prob mass
    is < top_p. For top_p >= 1 the bisection converges toward 0, keeping all
    tokens except those with probability below ~max_p * 2^-32 (which the old
    sort-based cumsum also effectively never sampled)."""
    probs = jax.nn.softmax(logits, axis=-1)
    lo = jnp.zeros(probs.shape[0])  # prob-space threshold
    hi = jnp.max(probs, axis=-1)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        mass_above = jnp.sum(jnp.where(probs > mid[:, None], probs, 0.0), axis=-1)
        ok = mass_above < top_p  # mid admits the whole nucleus -> go lower
        return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

    lo, hi = jax.lax.fori_loop(0, _BISECT_ITERS, body, (lo, hi))
    # hi is the smallest valid prob threshold; the call site compares in
    # prob space and keeps the smallest logit that passes
    return hi[:, None], probs


def masks_wanted(top_k, top_p, active=None) -> tuple:
    """Which threshold searches a batch asks for: ``(top-k, top-p)``, true
    when some LIVE lane set ``top_k > 0`` / ``top_p < 1.0``. ``active``
    [S] bool says which lanes are live (``None``: all of them); a finished
    request's values stay in its slot until the slot is reused, and a dead
    lane's token is thrown away, so it must not turn a search on. Written
    on array methods alone: the engine asks the same question of the same
    numbers on the host (numpy) when it counts ``stats()["sampling"]``."""
    live = True if active is None else active
    return (live & (top_k > 0)).any(), (live & (top_p < 1.0)).any()


def speculative_accept(
    logits: jax.Array,  # [S, T, V] float32 — logits[s, i] scores the token AFTER inputs[s, i]
    inputs: jax.Array,  # [S, T] int32 — row 0 is the last sampled token, rest the draft
    n_input: jax.Array,  # [S] int32 — valid prefix of ``inputs`` (1 + draft length)
    active: jax.Array,  # [S] bool — inactive lanes emit nothing
    rng: jax.Array,
    temperature: jax.Array,  # [S]
    top_k: jax.Array,  # [S] int32
    top_p: jax.Array,  # [S] float32
    stop_tokens: tuple,  # static: emission halts AFTER a stop token
    budgets: jax.Array,  # [S] int32 — sampled tokens remaining INCLUDING this dispatch's
    force_reject: jax.Array,  # [] bool — fault injection: treat every draft as mismatched
    constrain_fn=None,  # (logits [S, V], con_state [S], budget [S]) -> logits
    advance_fn=None,  # (con_state [S], toks [S], take [S] bool) -> con_state
    con_states: jax.Array = None,  # [S] int32
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Vectorized accept for speculative decoding (one verify dispatch).

    Walks the T scored positions per lane: at each position the token is
    sampled from the VERIFIED logits (greedy = argmax, so greedy emission is
    exactly the non-speculative engine's choice); emission continues to the
    next position only while the sampled token equals the drafted one — the
    first mismatch emits the corrected token and stops. Every emitted token
    is therefore distributed exactly as ancestral sampling from the model;
    the draft only decides how many positions land per dispatch. Rollback is
    implicit: the caller advances ``seq_len`` by the emitted count and the
    rejected tail's KV is dead (never read — attention masks by position).

    Returns ``(out_tokens [S, T], n_emit [S], con_states [S])`` where
    ``out_tokens[s, : n_emit[s]]`` are the committed tokens (-1 padded) and
    ``con_states`` advanced over exactly the emitted tokens.
    """
    S, T, V = logits.shape
    if con_states is None:
        con_states = jnp.zeros((S,), jnp.int32)
    # draft candidate for position i is the NEXT input token (shifted left)
    cand = jnp.concatenate(
        [inputs[:, 1:], jnp.zeros((S, 1), inputs.dtype)], axis=1
    )

    wanted = masks_wanted(top_k, top_p, active)

    def step(carry, xs):
        emitting, state, budget, rng = carry
        logits_i, cand_i, has_draft = xs
        l = constrain_fn(logits_i, state, budget) if constrain_fn is not None else logits_i
        rng, sub = jax.random.split(rng)
        tok = sample(l, sub, temperature, top_k, top_p, wanted)
        out_i = jnp.where(emitting, tok, -1)
        take = emitting
        budget = budget - take.astype(budget.dtype)
        if advance_fn is not None:
            state = advance_fn(state, tok, take)
        is_stop = jnp.zeros_like(emitting)
        for st in stop_tokens:
            is_stop = is_stop | (tok == st)
        match = has_draft & (tok == cand_i) & ~force_reject
        emitting = take & match & ~is_stop & (budget > 0)
        return (emitting, state, budget, rng), out_i

    has_draft = (jnp.arange(T)[:, None] + 1) < n_input[None, :]  # [T, S]
    (_, state, _, _), outs = jax.lax.scan(
        step,
        (active, con_states, budgets, rng),
        (jnp.swapaxes(logits, 0, 1), cand.T, has_draft),
    )
    out_tokens = outs.T  # [S, T]
    n_emit = jnp.sum(out_tokens >= 0, axis=1).astype(jnp.int32)
    return out_tokens, n_emit, state


def masked_logits(
    logits: jax.Array,  # [S, V] float32
    top_k: jax.Array,  # [S] int32, 0 = disabled
    top_p: jax.Array,  # [S] float32, 1.0 = disabled
    wanted: tuple | None = None,  # masks_wanted(...) of the batch's live lanes
) -> jax.Array:
    """``logits`` with what top-k and top-p leave out at ``NEG_INF``: the
    support :func:`sample` draws from, and what a speculative accept holds
    a drafted distribution against.

    Each threshold search runs only when the batch asks for it
    (``wanted``: computed here over all lanes for a caller with no
    ``active`` row, and by the caller once where it samples in a loop whose
    rows do not change). A batch in which no live lane asks keeps the
    logits unmasked, which is what 0 and 1.0 mean above; one in which some
    lane asks runs that search for the whole batch, as it always did.

    How each is switched off is what the chip's compiler made of it
    (tests/engine/test_chip_compile.py holds both): the top-k search is its
    own loop making no turn, because a conditional around a loop over the
    logits moved them out of the fast memory every later pass reads them
    from; the top-p search, which needs a softmax first, is one
    ``lax.cond`` that hands back a LOGIT-space threshold a row, so what
    crosses its edge is rank 1 and its skipping branch writes nothing."""
    logits = logits.astype(jnp.float32)
    S, V = logits.shape
    want_k, want_p = masks_wanted(top_k, top_p) if wanted is None else wanted

    # top-k mask: keep the k largest (k==0 -> keep all)
    k = jnp.where(top_k > 0, top_k, V)
    kth = _topk_threshold(logits, k, jnp.where(want_k, _BISECT_ITERS, 0))[:, 0]

    # top-p (nucleus) mask over what top-k left. The search compares in
    # probability space; the smallest kept logit selects the same set
    # (softmax is monotone in the logit, ties included) and is at or above
    # kth, so one compare applies both masks
    def nucleus():
        kept = jnp.where(logits < kth[:, None], NEG_INF, logits)
        p_thresh, probs = _topp_threshold(kept, top_p)
        return jnp.min(jnp.where(probs < p_thresh, jnp.inf, kept), axis=-1)

    thresh = jax.lax.cond(want_p, nucleus, lambda: kth)
    # without the barrier the compiler moves the broadcast below into the
    # conditional, whose skipping branch then writes [S, V] of it
    thresh = jax.lax.optimization_barrier(thresh)
    return jnp.where(logits < thresh[:, None], NEG_INF, logits)


def sample(
    logits: jax.Array,  # [S, V] float32
    rng: jax.Array,
    temperature: jax.Array,  # [S]
    top_k: jax.Array,  # [S] int32, 0 = disabled
    top_p: jax.Array,  # [S] float32, 1.0 = disabled
    wanted: tuple | None = None,  # masks_wanted(...) of the batch's live lanes
) -> jax.Array:
    """Returns sampled token ids [S]: greedy at temperature 0, else a draw
    from ``categorical(masked_logits / temperature)``."""
    logits = masked_logits(logits, top_k, top_p, wanted)
    greedy = jnp.argmax(logits, axis=-1)
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    sampled = jax.random.categorical(rng, logits / temp, axis=-1)
    return jnp.where(temperature <= 0.0, greedy, sampled).astype(jnp.int32)


def speculative_sample(
    p_logits: jax.Array,  # [S, 2, V] float32, masked: row 0 scores the draft, row 1 the token after it
    q_logits: jax.Array,  # [S, V] float32, masked: the drafter's, which ``draft`` was drawn from
    draft: jax.Array,  # [S] int32
    rng: jax.Array,
    temperature: jax.Array,  # [S]
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Speculative sampling proper, for a drafted DISTRIBUTION ``q`` (a
    model's own drafter) where :func:`speculative_accept` tests a point
    draft for equality: the draft is kept with probability ``min(1, p(d) /
    q(d))``; a refused draft is replaced by a draw from ``norm(max(p - q,
    0))``; after a kept draft the next token is drawn from row 1's ``p``.
    The first token is then distributed exactly as ``p`` (Leviathan et al.,
    Chen et al. 2023), whatever ``q`` is. At temperature 0 both
    distributions are points: the draft is kept when it is ``p``'s argmax
    and replaced by that argmax when not, which is the equality test.
    -> (kept [S] bool, first [S]: the draft where kept, else its
    replacement, next [S]: row 1's draw, meaningful where kept)."""
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    greedy = temperature <= 0.0
    k_accept, k_resid, k_next = jax.random.split(rng, 3)
    p0 = jax.nn.softmax(p_logits[:, 0] / temp, axis=-1)
    q = jax.nn.softmax(q_logits / temp, axis=-1)
    at = lambda a: jnp.take_along_axis(a, draft[:, None], axis=-1)[:, 0]  # noqa: E731
    u = jax.random.uniform(k_accept, draft.shape, jnp.float32)
    kept = u * at(q) <= at(p0)  # u <= p/q without the division; q(d) > 0 for a token drawn from q
    resid = jnp.maximum(p0 - q, 0.0)
    # p == q to the last bit leaves no residual, and is never refused
    resid_logits = jnp.where(jnp.sum(resid, axis=-1, keepdims=True) > 0, jnp.log(resid), p_logits[:, 0] / temp)
    replaced = jax.random.categorical(k_resid, resid_logits, axis=-1)
    nxt = jax.random.categorical(k_next, p_logits[:, 1] / temp, axis=-1)
    top = jnp.argmax(p_logits, axis=-1)  # [S, 2]
    kept = jnp.where(greedy, draft == top[:, 0], kept)
    first = jnp.where(greedy, top[:, 0], jnp.where(kept, draft, replaced))
    nxt = jnp.where(greedy, top[:, 1], nxt)
    return kept, first.astype(jnp.int32), nxt.astype(jnp.int32)
