"""K-EXAONE's verify-and-draft step through both caches, against the plain
reference (a file of its own beside `test_exaone.py`, whose helpers it takes:
eight patterns and three controls each compile the family's cache check):
prefill, then verify steps under every pattern of kept and refused drafts of
three steps, main logits and drafted logits; each of the cache's controls
seen. CPU, tiny sizes, float32, seeded weights."""

import functools
import itertools

import jax.numpy as jnp
import numpy as np
import pytest

from acpbench import check
from tests.engine.test_exaone import PAGE, built, tiny

def _sample(config, seed=11, steps=6):
    config = dict(config, check=dict(config["check"], decode_steps=steps))
    return config, check.sample(config["check"], config["vocab_size"], PAGE, seed)


@pytest.mark.parametrize("kept", list(itertools.product([True, False], repeat=3)),
                         ids=lambda k: "".join("k" if x else "r" for x in k))
def test_verify_steps_agree_with_the_reference_under_every_pattern_of_three_steps(kept):
    """Prefill, then verify steps whose first three drafts are kept or
    refused as the pattern says (a refused draft is another token, rolled
    back by count): every compared row of the stack's logits, and the
    drafted logits each step starts from, against the plain reference's full
    pass. Prompts lie past the 16-token window: the ring has wrapped."""
    config, s = _sample(tiny())
    family, pc, mesh, params = built(config, seed=2**31 + 7)
    B, N = s["B"], s["N"]
    # what a sequence's rows meet in order: its first three drafts follow `kept`, the rest are kept
    pattern = np.ones((B, N), bool)
    at = 0
    for k in kept:
        pattern[:, at] = k
        at += 2 if k else 1
    pre, dec, drafted, started = family.cached_logits(config, pc, params, mesh, s, False, draft=True, kept=pattern)
    want = check.reference_logits(functools.partial(family.reference_logits, config, params), s)
    numbers = check.compare((pre, dec), want)
    assert numbers["finite"] and numbers["top1_agree"] == 1.0
    assert numbers["prefill_rel_rms"] < 2e-5 and numbers["decode_rel_rms"] < 2e-5, numbers
    rows = s["lengths"][:, None] - 1 + np.arange(N)[None, :]
    want_q = family.reference_draft_logits(config, params, s["tokens"], rows)
    m = jnp.asarray(started)[..., None]
    assert started[:, 0].all() and started.sum() >= B * 3
    rel = float(jnp.sqrt(jnp.sum(jnp.where(m, (drafted - want_q) ** 2, 0.0)) / jnp.sum(jnp.where(m, want_q ** 2, 0.0))))
    assert rel < 2e-5, rel


@pytest.mark.parametrize("control", ["window_minus_page", "draft_row_kept", "kv_int8"])
def test_each_cache_control_is_seen(control):
    config, s = _sample(tiny(), steps=8)
    family, pc, mesh, params = built(config)
    want = check.reference_logits(functools.partial(family.reference_logits, config, params), s)
    sound = check.compare(family.cached_logits(config, pc, params, mesh, s, False), want)
    wrong = check.compare(family.cached_logits(config, pc, params, mesh, s, False, **{control: True}), want)
    assert sound["decode_rel_rms"] < 2e-5 and wrong["decode_rel_rms"] > 1e-3, (control, sound, wrong)
