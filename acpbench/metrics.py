"""Arithmetic shared by the metric readers: per-request gaps, percentiles
with failures counted as missing, and the distribution line each run
prints so that a two-mode metric is seen before it is bounded."""

from __future__ import annotations

import math
import statistics


def request_gap_ms(rec) -> float | None:
    """Mean gap between one request's output tokens on the benchmark's
    clock: (t_last_token - t_first_token) / (tokens - 1). A request
    averages over all its decode blocks, so whether a block ran behind
    another request's prefill averages out inside the sample."""
    if rec.error is not None or rec.n_tokens < 2 or rec.first_t is None:
        return None
    return (rec.last_t - rec.first_t) / (rec.n_tokens - 1) * 1e3


def percentile(values: list[float], q: float, missing: int = 0, missing_value: float = math.inf) -> float | None:
    """Nearest-rank percentile over `values` plus `missing` samples that
    lie beyond any value (failed or refused requests)."""
    n = len(values) + missing
    if n == 0:
        return None
    rank = max(1, math.ceil(q / 100.0 * n))
    ordered = sorted(values)
    return ordered[rank - 1] if rank <= len(ordered) else missing_value


def distribution(values: list[float]) -> dict:
    """min, quartiles, max and count: what a bound is judged against."""
    if not values:
        return {"n": 0}
    v = sorted(values)
    if len(v) >= 2:
        q1, q2, q3 = statistics.quantiles(v, n=4)
    else:
        q1 = q2 = q3 = v[0]
    return {"n": len(v), "min": v[0], "q1": q1, "median": q2, "q3": q3, "max": v[-1]}


def in_window(t: float | None, window: tuple[float, float]) -> bool:
    return t is not None and window[0] <= t < window[1]


def tokens_in_window(records, window: tuple[float, float]) -> float:
    """Output tokens produced inside the window. The engine hands tokens
    over a decode block at a time, so a request's emission of n tokens at
    t stands for n tokens produced since its emission before (since it was
    sent, for its first): they are spread evenly over that interval and
    counted by the part of it that lies inside the window. Counting whole
    emissions by their stamps instead moves the count a block (all slots x
    8 tokens, 0.9% of a 51 s window) with whichever side of the window's
    edge a block's end falls on."""
    lo, hi = window
    total = 0.0
    for r in records:
        prev = r.sent if r.sent is not None else r.due
        for t, n in r.blocks:
            if t > prev:
                total += n * max(0.0, min(t, hi) - max(prev, lo)) / (t - prev)
            elif lo <= t < hi:
                total += n
            prev = max(prev, t)
    return total


def cycle_intervals_ms(records, window: tuple[float, float], apart_s: float = 0.005) -> list[float]:
    """Time from one hand-over of tokens to the next, inside the window:
    the engine's cycles as the callers see them (a decode block, or a
    prefill's first tokens). Stamps closer than `apart_s` are one
    hand-over. A run that is slow because the host was (every interval a
    little longer) reads differently here from one that took another
    course (another number of intervals)."""
    stamps = sorted(t for r in records for t, _ in r.blocks if in_window(t, window))
    if not stamps:
        return []
    starts = [stamps[0]] + [b for a, b in zip(stamps, stamps[1:]) if b - a > apart_s]
    return [(b - a) * 1e3 for a, b in zip(starts, starts[1:])]


def first_event_percentile(run, q: float) -> float | None:
    """Percentile q of (first token - due) in ms over the requests due
    inside the window. One that failed or had no first token lies beyond
    any percentile; if the percentile lands there it reads the drain limit,
    the longest a request is waited for."""
    due = [r for r in run.records if in_window(r.due, run.window)]
    got = [(r.first_t - r.due) * 1e3 for r in due if r.first_t is not None and r.error is None]
    return percentile(got, q, missing=len(due) - len(got),
                      missing_value=float(run.mix["drain_limit_s"]) * 1e3)


def window_samples(run) -> tuple[list[float], list[float]]:
    """(per-request gaps of the requests that ended inside the window,
    first-event latencies of those due inside it), in ms: what the
    distribution lines print and the sweep reads."""
    gaps = [g for g in (request_gap_ms(r) for r in run.records if in_window(r.end_t, run.window))
            if g is not None]
    firsts = [(r.first_t - r.due) * 1e3 for r in run.records
              if in_window(r.due, run.window) and r.first_t is not None]
    return gaps, firsts
