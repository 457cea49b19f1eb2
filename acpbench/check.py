"""Decides `correct`, outside the window. Knows no model family: the
program's side and the reference's come from the module the
configuration's file names (`acpbench/families/`).

The program's own model programs (the family's `cached_logits`: its
prefill, then its decode step through its cache with the kernels the
engine uses, on the engine's mesh) run a seeded sample of sequences:
prefill, then teacher-forced decode steps through the cache. Their logits
are held against the family's plain reference, a float32 pass over whole
sequences (`reference` below: `(tokens, rows, lower=None) -> logits`, the
family's `reference_logits` over a run's configuration and weights).
Logits, not tokens: with random weights the largest logit changes on
rounding.

The cell's own path is held to the reference too: the sample's prompts go
through `submit` as greedy requests, admitted as one group, so that the
engine's scheduling, page allocation, decode block, page walk and token
hand-over produce them, and each token it emits is looked up in the
reference's logits for that request's own prompt and tokens so far.

Four numbers are compared, each beside its limit:

`logit_rel_rms`   ||program - reference|| / ||reference|| over every
    compared logit (the prefill's row and the decode rows of each
    sequence): millions of samples, steady from seed to seed. It catches a
    wrong result and a matmul in a precision well under bfloat16.
`cache_excess`    the decode rows are also computed by the prefill program
    (the same prompts, one token longer each time), so each row has two
    readings against the one reference: through the cache and not. Their
    squared errors differ by what the cache path adds, as a share of the
    prefill path's: (sum|dec - ref|^2 - sum|pre - ref|^2) / sum|pre - ref|^2.
    bfloat16 pages add nothing (about 0); int8 pages add their rounding,
    which `logit_rel_rms` cannot see because it is no larger than
    bfloat16's own through 28 layers.

`greedy_regret`   the largest, over the tokens the engine emitted, of how
    far the reference's logit of the emitted token lies under the
    reference's largest at that position, in standard deviations of that
    position's logits. A greedy engine reading its own request's pages
    emits the reference's first choice, or one so close that bfloat16
    decides (about 0.05). A token decoded from another request's page, a
    token dropped from or doubled in a block, or a stale page table reads
    as a token the reference ranks far down (the control: a request one of
    whose pages holds another request's tokens).
`stream_mismatch` requests whose tokens as streamed through `on_tokens`
    differ from those of their result, or that ended neither on a stop
    token nor at their budget; exact, limit 0. (The engine hands over no
    stop token, streamed or returned, so a request that stops at once
    emits nothing and is no mismatch.)

The limits sit in the configuration's file; PERF.md section 2 has the
readings they were set from.
"""

from __future__ import annotations

import numpy as np


def sample(spec: dict, vocab: int, page_size: int, seed: int) -> dict:
    """The sequences: B prompts of seeded lengths and ids, N forced tokens
    each, and the pages they live in."""
    rng = np.random.default_rng([seed, 3])
    B, T, N = spec["sequences"], spec["prefill_bucket"], spec["decode_steps"]
    lengths = rng.integers(spec["min_prompt"], T - N + 1, size=B).astype(np.int32)
    lengths[0] = T - N  # one sequence grows to fill the bucket
    tokens = rng.integers(0, vocab, size=(B, T + N)).astype(np.int32)
    per_seq = -(-(T + N) // page_size)
    tables = (1 + np.arange(B * per_seq, dtype=np.int32)).reshape(B, per_seq)
    rows = lengths[:, None] - 1 + np.arange(N + 1)[None, :]
    return {"B": B, "T": T, "N": N, "P": page_size, "lengths": lengths, "tokens": tokens,
            "tables": tables, "rows": rows, "pool_pages": B * per_seq + 1}


def page_ids(s: dict, lengths) -> np.ndarray:
    """[B, T // P] pages a prefill of `lengths` writes; 0 is the trash page."""
    out = np.zeros((s["B"], s["T"] // s["P"]), dtype=np.int32)
    for b in range(s["B"]):
        n = -(-int(lengths[b]) // s["P"])
        out[b, :n] = s["tables"][b, :n]
    return out


def engine_path(system, s: dict, n_tokens: int) -> dict:
    """The sample's prompts as greedy requests through the system's own
    `submit`, admitted together: what each emitted, as streamed and as
    returned."""
    streamed = [[] for _ in range(s["B"])]
    with system.held():
        futures = [
            system.submit({"prompt": s["tokens"][b, : s["lengths"][b]].tolist(), "max_tokens": n_tokens,
                           "temperature": 0.0}, streamed[b].extend)
            for b in range(s["B"])
        ]
    results = [f.result(timeout=600) for f in futures]
    return {"streamed": [list(map(int, t)) for t in streamed],
            "returned": [list(map(int, r.tokens)) for r in results],
            "finish": [r.finish_reason for r in results], "budget": n_tokens}


def swapped_page(s: dict, tokens: np.ndarray) -> np.ndarray:
    """The structural control's input: each sequence's second page holds
    the next sequence's tokens, as a wrong page id would have it."""
    out, P = tokens.copy(), s["P"]
    out[:, P: 2 * P] = np.roll(tokens[:, P: 2 * P], 1, axis=0)
    return out


def engine_numbers(reference, s: dict, path: dict, control: bool = False) -> dict:
    """`greedy_regret` and `stream_mismatch` of what the engine emitted.
    With `control`, the emitter judged is the reference itself reading a
    swapped page (its first choice at every position, the engine's tokens
    as context), in the engine's place."""
    import jax.numpy as jnp

    emitted = path["returned"]
    R = max(1, max(len(e) for e in emitted))
    width = max(s["tokens"].shape[1], int(s["lengths"].max()) + R)
    tokens = np.zeros((s["B"], width), dtype=np.int32)
    valid = np.zeros((s["B"], R), dtype=bool)
    picked = np.zeros((s["B"], R), dtype=np.int32)
    for b, e in enumerate(emitted):
        n = int(s["lengths"][b])
        tokens[b, :n] = s["tokens"][b, :n]
        tokens[b, n: n + len(e)] = e
        valid[b, : len(e)], picked[b, : len(e)] = True, e
    rows = s["lengths"][:, None] - 1 + np.arange(R)[None, :]
    want = reference(tokens, rows)
    if control:
        picked = np.asarray(jnp.argmax(reference(swapped_page(s, tokens), rows), -1))
    chosen = jnp.take_along_axis(want, jnp.asarray(picked)[..., None], axis=-1)[..., 0]
    regret = (jnp.max(want, -1) - chosen) / jnp.std(want, -1)
    mismatch = sum(
        1 for st, re, fin in zip(path["streamed"], emitted, path["finish"])
        if st != re or (fin != "stop" and len(re) != path["budget"])
    )
    return {
        "greedy_regret": float(jnp.max(jnp.where(jnp.asarray(valid), regret, 0.0))),
        "stream_mismatch": mismatch,
        "engine_tokens": int(valid.sum()),
        "engine_top1_agree": float(jnp.sum((regret == 0) & jnp.asarray(valid)) / max(1, valid.sum())),
    }


def reference_logits(reference, s: dict, lower: str | None = None):
    # sequence b is tokens[b, : length + N]; what lies beyond is never read
    # by a compared row (causal), so the one [B, T + N] array serves all
    return reference(s["tokens"], s["rows"], lower=lower)


def compare(got, want) -> dict:
    """`got` is (pre, dec) as a family's `cached_logits` gives them, or one
    [B, N+1, V] array from a reference in a lower precision, which has no
    cache and reads 0 for `cache_excess`."""
    import jax.numpy as jnp

    pre, dec = got if isinstance(got, tuple) else (got, got[:, 1:])
    served = jnp.concatenate([pre[:, :1], dec], axis=1)  # what a request is served from
    sq = lambda a: float(jnp.sum(a * a))  # noqa: E731
    through_cache, beside_it = sq(dec - want[:, 1:]), sq(pre[:, 1:] - want[:, 1:])
    return {
        "logit_rel_rms": (sq(served - want) / sq(want)) ** 0.5,
        "cache_excess": (through_cache - beside_it) / beside_it if beside_it else 0.0,
        "prefill_rel_rms": (sq(pre[:, 0] - want[:, 0]) / sq(want[:, 0])) ** 0.5,
        "decode_rel_rms": (through_cache / sq(want[:, 1:])) ** 0.5,
        "max_abs_over_range": float(jnp.max(jnp.abs(served - want)) / jnp.max(jnp.abs(want))),
        "top1_agree": float(jnp.mean(jnp.argmax(served, -1) == jnp.argmax(want, -1))),
        "finite": bool(jnp.all(jnp.isfinite(served))),
    }


def decide(numbers: dict, limits: dict) -> tuple[bool, list[str]]:
    """Each number compared, beside its limit."""
    ok = bool(numbers.get("finite", False))
    lines = [f"finite={numbers.get('finite')}"]
    for name, limit in limits.items():
        value = numbers[name]
        good = bool(value <= limit)
        ok = ok and good
        lines.append(f"{name}={value:.6g} limit={limit:.6g} {'ok' if good else 'EXCEEDED'}")
    return ok, lines
