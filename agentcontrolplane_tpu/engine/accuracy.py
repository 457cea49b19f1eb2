"""Byte-identity-relaxed accuracy gate for quantized serving.

Greedy byte-identity is this repo's load-bearing correctness contract:
every serving mechanism (chunking, spec decode, megastep fusion, KV
tiers) is pinned bit-for-bit against the plain path. Quantization is the
one knob that LEGITIMATELY breaks it — int8 weights and int8 KV are a
different (deliberately close) function. This module is the replacement
contract: a pinned deterministic fixture is scored through the REAL
serving numerics (prefill writes + per-step decode reads against the
slot cache, exactly the hot loop's read/write discipline) under the
quantized configuration and under the bf16 baseline, and the gate
asserts

- **top-1 greedy agreement** — the fraction of positions whose argmax
  token matches the bf16 path — stays >= a pinned threshold, and
- **logit MAE** — mean |quantized - bf16| over the fixture's logits —
  stays <= a pinned bound.

Tests pin the thresholds (tests/engine/test_quant_kv.py). Both knobs off
remains covered by the existing byte-identity matrix — this gate never
relaxes that.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.llama import (
    LlamaConfig,
    decode_step,
    init_kv_cache,
    prefill_batch,
)
from ..ops.quant import quantize_params


def pinned_fixture(
    vocab_size: int, prompts: int = 4, length: int = 48, seed: int = 20260804
) -> np.ndarray:
    """The gate's deterministic prompt set: ``[prompts, length]`` int32
    rows drawn from a fixed seed (token 0 reserved out, matching the
    tokenizers' pad/special conventions). Same (vocab, shape, seed) ->
    same fixture forever — changing any of these is changing the
    contract, not re-rolling it."""
    rng = np.random.default_rng(seed)
    return rng.integers(1, vocab_size, size=(prompts, length)).astype(np.int32)


@lru_cache(maxsize=8)
def _jitted(config: LlamaConfig):
    # one jitted pair per config: a fresh jax.jit wrapper per call would
    # recompile every shape on every report (LlamaConfig is frozen/hashable)
    return (
        jax.jit(partial(prefill_batch, config=config)),
        jax.jit(partial(decode_step, config=config)),
    )


def teacher_forced_logits(
    params: dict,
    config: LlamaConfig,
    rows: np.ndarray,  # [B, T] int32 — equal-length fixture rows
    quantize_kv: bool = False,
) -> np.ndarray:
    """Serving-numerics logits at every position: the first token prefills
    a (optionally int8) slot cache, then each following token is teacher-
    forced through ``decode_step`` — so position ``t``'s logits are
    computed reading the cache exactly as the engine's decode loop reads
    it (quantized rows dequantize after the gather; fresh K/V quantizes on
    commit). Returns [B, T, V] float32; ``logits[:, t]`` scores the token
    following ``rows[:, t]``."""
    B, T = rows.shape
    cache = init_kv_cache(config, B, T, quantize_kv=quantize_kv)
    slots = jnp.arange(B, dtype=jnp.int32)
    ones = jnp.ones(B, dtype=jnp.int32)
    active = jnp.ones(B, dtype=bool)
    jit_prefill, jit_decode = _jitted(config)
    cache, logits = jit_prefill(
        params, cache, jnp.asarray(rows[:, :1]), ones, slots
    )
    out = [np.asarray(logits)]
    for t in range(1, T):
        cache, logits = jit_decode(
            params, cache,
            jnp.asarray(rows[:, t]),
            jnp.full((B,), t, dtype=jnp.int32),
            active=active,
        )
        out.append(np.asarray(logits))
    return np.stack(out, axis=1).astype(np.float32)


def accuracy_report(
    config: LlamaConfig,
    params: dict,
    *,
    quantize_weights: bool = False,
    quantize_kv: bool = False,
    rows: Optional[np.ndarray] = None,
    baseline: Optional[np.ndarray] = None,
) -> dict:
    """Score one quantized configuration against the bf16 baseline over
    the pinned fixture. ``params`` are the DENSE params (the weight-
    quantized run derives its int8 copy via ``quantize_params``, so both
    runs serve the same underlying function). ``baseline`` optionally
    supplies the bf16 :func:`teacher_forced_logits` for these ``rows``
    (callers scoring several configurations pay the baseline pass once).
    Returns the gate metrics::

        {"top1_agreement": float, "logit_mae": float,
         "positions": int, "quantize_weights": bool, "quantize_kv": bool}
    """
    if rows is None:
        rows = pinned_fixture(config.vocab_size)
    base = baseline if baseline is not None else teacher_forced_logits(
        params, config, rows, quantize_kv=False
    )
    qparams = quantize_params(params) if quantize_weights else params
    cand = teacher_forced_logits(qparams, config, rows, quantize_kv=quantize_kv)
    agree = float(np.mean(base.argmax(-1) == cand.argmax(-1)))
    mae = float(np.mean(np.abs(base - cand)))
    return {
        "top1_agreement": round(agree, 4),
        "logit_mae": round(mae, 5),
        "positions": int(base.shape[0] * base.shape[1]),
        "quantize_weights": bool(quantize_weights),
        "quantize_kv": bool(quantize_kv),
    }


def check_accuracy_gate(
    report: dict, min_top1: float, max_logit_mae: float
) -> list[str]:
    """Evaluate a report against pinned thresholds; returns violations
    (empty = the gate passes). Split from :func:`accuracy_report` so a
    caller can keep the numbers AND the gate verdict."""
    problems: list[str] = []
    if report["top1_agreement"] < min_top1:
        problems.append(
            f"top-1 greedy agreement {report['top1_agreement']} < pinned "
            f"threshold {min_top1}"
        )
    if report["logit_mae"] > max_logit_mae:
        problems.append(
            f"logit MAE {report['logit_mae']} > pinned bound {max_logit_mae}"
        )
    return problems
