"""Open loop: independent users. Requests are due on the mix's schedule whatever
the system does, and each is timed from its due time. The schedule is one
fixed trace, not a draw per run: the gaps are the mid-quantiles of a
Poisson process's gaps in the order the mix's `shape_seed` gives (see
_shapes.py), so one arrival order is judged. The seed gives the token ids.

Mix parameters: `rate_per_s`, `ramp_s` (arrivals before the window opens,
so the window sees a running system), `prompt_tokens` / `answer_tokens`
(size distributions), `temperature`, `prompt_vocab`.
"""

from __future__ import annotations

import numpy as np

from . import _shapes


def plan(mix: dict, seed: int, seconds: float, config: dict) -> dict:
    rng, order = np.random.default_rng([seed, 1]), _shapes.order_rng(mix, 1)
    horizon = mix["ramp_s"] + seconds
    n = max(1, round(mix["rate_per_s"] * horizon))
    gaps = _shapes.shuffled(order, _shapes.exponential_gaps(n, mix["rate_per_s"]))
    prompts = _shapes.shuffled(order, _shapes.sizes(mix["prompt_tokens"], n))
    answers = _shapes.shuffled(order, _shapes.sizes(mix["answer_tokens"], n))
    due = np.cumsum(gaps) - gaps[0] * 0.5
    requests = [
        {
            "due_s": float(due[i]),
            "prompt": _shapes.token_ids(rng, prompts[i], mix["prompt_vocab"]),
            "max_tokens": answers[i],
            "temperature": mix["temperature"],
        }
        for i in range(n) if due[i] < horizon
    ]
    return {"mode": "open", "ramp_s": mix["ramp_s"], "requests": requests}
