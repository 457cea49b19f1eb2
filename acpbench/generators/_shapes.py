"""A mix is a trace drawn once: the n sizes (and arrival gaps) are the n
mid-quantiles of the stated distribution, put in an order by the mix's own
`shape_seed`. `--seed` decides the contents (token ids, texts, weights)
and nothing that the clock sees.

Why so fixed: with the order left to `--seed`, six runs of q7b-chat-mixed
(same sizes, same gaps, six orders) spread by 21% in `ttft_p90_ms` and 9%
in `gap_p50_ms` (my chip run, PR 25, call 3): at four fifths of the knee
the order of arrivals decides who queues behind whom. That spread was the
traffic's, not the system's. A trace replayed is what a serving benchmark
does with a recorded production trace.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def quantile_points(n: int) -> list[float]:
    return [(i + 0.5) / n for i in range(n)]


def lognormal(n: int, median: float, sigma: float, lo: int, hi: int) -> list[int]:
    nd = NormalDist()
    return [int(min(hi, max(lo, round(median * math.exp(sigma * nd.inv_cdf(u)))))) for u in quantile_points(n)]


def uniform(n: int, lo: int, hi: int) -> list[int]:
    return [int(round(lo + u * (hi - lo))) for u in quantile_points(n)]


def exponential_gaps(n: int, rate: float) -> list[float]:
    """n gaps of a Poisson process at `rate`, scaled to sum to n / rate."""
    raw = [-math.log(1.0 - u) for u in quantile_points(n)]
    scale = (n / rate) / sum(raw)
    return [g * scale for g in raw]


def sizes(spec: dict, n: int) -> list[int]:
    if spec["dist"] == "lognormal":
        return lognormal(n, spec["median"], spec["sigma"], spec["min"], spec["max"])
    if spec["dist"] == "uniform":
        return uniform(n, spec["min"], spec["max"])
    if spec["dist"] == "fixed":
        return [int(spec["value"])] * n
    raise ValueError(f"unknown size distribution {spec['dist']!r}")


def order_rng(mix: dict, stream: int) -> np.random.Generator:
    """The generator that orders a mix's trace: the mix's, not the run's."""
    return np.random.default_rng([int(mix.get("shape_seed", 0)), stream])


def shuffled(rng: np.random.Generator, items: list) -> list:
    return [items[i] for i in rng.permutation(len(items))]


def token_ids(rng: np.random.Generator, n: int, vocab: int) -> list[int]:
    return rng.integers(0, vocab, size=n).tolist()
