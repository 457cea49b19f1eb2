"""Closed loop: `clients` callers that each wait for a reply before they
send again, so the system is always full and a slow system gets less load.

Each client's first answer is cut to a share of its length, the shares
spread evenly over (0, 1) and dealt by the mix's `shape_seed`, so that the
slots are out of phase when the window opens. Sizes and their order are the
mix's (see _shapes.py); the seed gives the token ids. Mix parameters: `clients`, `ramp_s`,
`prompt_tokens`, `answer_tokens`, `temperature`, `prompt_vocab`,
`requests_per_client` (enough to outlast ramp + window at any speed).
"""

from __future__ import annotations

import numpy as np

from . import _shapes


def plan(mix: dict, seed: int, seconds: float, config: dict) -> dict:
    rng, order = np.random.default_rng([seed, 2]), _shapes.order_rng(mix, 2)
    c, per = mix["clients"], mix["requests_per_client"]
    n = c * per
    prompts = _shapes.shuffled(order, _shapes.sizes(mix["prompt_tokens"], n))
    answers = _shapes.shuffled(order, _shapes.sizes(mix["answer_tokens"], n))
    shares = _shapes.shuffled(order, _shapes.quantile_points(c))
    clients = []
    for k in range(c):
        seq = []
        for j in range(per):
            i = k * per + j
            tokens = answers[i] if j else max(2, round(answers[i] * shares[k]))
            seq.append({
                "prompt": _shapes.token_ids(rng, prompts[i], mix["prompt_vocab"]),
                "max_tokens": tokens,
                "temperature": mix["temperature"],
            })
        clients.append(seq)
    return {"mode": "closed", "ramp_s": mix["ramp_s"], "clients": clients}
