"""Rotary position embeddings (RoPE), TPU-friendly formulation.

No reference analogue (the reference delegates model execution to SaaS —
SURVEY.md §0); this is part of the in-tree ``provider: tpu`` serving stack.

Uses the split-half convention (rotate_half), matching HF Llama so weights
load unmodified. Frequencies are computed on the fly from integer positions —
cheap on the VPU, avoids carrying a [max_seq, d] table through jit.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies [head_dim//2] (float32)."""
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def llama3_scale_frequencies(
    inv_freq: jax.Array,
    factor: float,
    low_freq_factor: float,
    high_freq_factor: float,
    original_max_seq: int,
) -> jax.Array:
    """Llama-3.1's published RoPE frequency rescale (HF
    ``rope_scaling.rope_type == "llama3"``): long wavelengths (beyond the
    original context) are slowed by ``factor``, short ones kept, with a
    smooth ramp between — how 3.1/3.2 checkpoints reach 128k context.
    Serving those checkpoints with UNscaled frequencies computes a
    different function than the one they were trained with."""
    two_pi = 2.0 * jnp.pi
    wavelen = two_pi / inv_freq
    low_wavelen = original_max_seq / low_freq_factor
    high_wavelen = original_max_seq / high_freq_factor
    smooth = (original_max_seq / wavelen - low_freq_factor) / (
        high_freq_factor - low_freq_factor
    )
    interpolated = (1.0 - smooth) * inv_freq / factor + smooth * inv_freq
    return jnp.where(
        wavelen < high_wavelen,
        inv_freq,
        jnp.where(wavelen > low_wavelen, inv_freq / factor, interpolated),
    )


def yarn_correction_range(
    head_dim: int, theta: float, original_max_seq: int, beta_fast: float, beta_slow: float
) -> tuple[int, int]:
    """(low, high): the pair indices between which YaRN's ramp runs. Pair
    ``k`` turns ``r`` times over the original context where ``k =
    head_dim ln(original / (2 pi r)) / (2 ln theta)``; ``low`` is the floor
    of that at ``beta_fast`` turns, ``high`` the ceiling at ``beta_slow``,
    both clipped to ``[0, head_dim - 1]``."""
    def dim_at(r: float) -> float:
        return head_dim * math.log(original_max_seq / (2.0 * math.pi * r)) / (2.0 * math.log(theta))

    low = max(math.floor(dim_at(beta_fast)), 0)
    high = min(math.ceil(dim_at(beta_slow)), head_dim - 1)
    return low, high


def yarn_scale_frequencies(
    inv_freq: jax.Array,
    factor: float,
    original_max_seq: int,
    beta_fast: float,
    beta_slow: float,
    theta: float,
) -> jax.Array:
    """YaRN's frequencies (HF ``rope_type == "yarn"``): pairs that turn
    often over the original context keep their frequency, pairs that turn
    less than once are slowed by ``factor``, a linear ramp over the pair
    index between (:func:`yarn_correction_range`). The attention factor
    that goes with them multiplies cos and sin (``apply_rope``'s
    ``yarn``)."""
    half = inv_freq.shape[0]
    low, high = yarn_correction_range(2 * half, theta, original_max_seq, beta_fast, beta_slow)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low) / max(high - low, 0.001), 0.0, 1.0)
    return (1.0 - ramp) * inv_freq + ramp * inv_freq / factor


def apply_rope(
    x: jax.Array,  # [..., T, H, d]
    positions: jax.Array,  # [..., T] int32; with `sections`, [..., len(sections), T]
    theta: float = 500000.0,
    scaling: "tuple[float, float, float, int] | None" = None,
    yarn: "tuple[float, int, float, float, float] | None" = None,
    sections: "tuple[int, ...] | None" = None,
) -> jax.Array:
    """Rotate q or k by position. Computed in float32, cast back.
    ``scaling`` = (factor, low_freq_factor, high_freq_factor,
    original_max_seq) applies the Llama-3.1 frequency rescale; ``yarn`` =
    (factor, original_max_seq, beta_fast, beta_slow, attention_factor)
    YaRN's, cos and sin times its attention factor. ``sections`` (the
    source's ``mrope_section``, summing to ``d / 2``) gives a token one
    position an axis (time, height, width: ``models/keye.py``): frequency
    ``i`` turns by the position of the axis whose section holds ``i``, the
    halves rotated as ever. Equal rows give the one-position result bit for
    bit: the same products of the same floats."""
    d = x.shape[-1]
    if sections is not None and (sum(sections) != d // 2 or positions.shape[-2] != len(sections)):
        raise ValueError(f"sections {sections} must sum to {d // 2} and positions {positions.shape} carry one row each")
    inv_freq = rope_frequencies(d, theta)  # [d/2]
    if scaling is not None:
        inv_freq = llama3_scale_frequencies(inv_freq, *scaling)
    if yarn is not None:
        inv_freq = yarn_scale_frequencies(inv_freq, *yarn[:4], theta)
    if sections is None:
        positions = positions[..., None]
    else:  # [..., A, T] -> [..., T, d/2]: each frequency's own axis' position
        axis_of = jnp.repeat(jnp.arange(len(sections)), jnp.asarray(sections), total_repeat_length=d // 2)
        positions = jnp.take(jnp.moveaxis(positions, -2, -1), axis_of, axis=-1)
    angles = positions.astype(jnp.float32) * inv_freq  # [..., T, d/2]
    cos = jnp.cos(angles)[..., None, :]  # [..., T, 1, d/2]
    sin = jnp.sin(angles)[..., None, :]
    if yarn is not None:
        cos, sin = cos * yarn[4], sin * yarn[4]
    x1 = x[..., : d // 2].astype(jnp.float32)
    x2 = x[..., d // 2 :].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def deinterleave_pairs(w: jax.Array) -> jax.Array:
    """The last axis' values in the order ``apply_rope`` pairs them: a
    source that rotates neighbours ``(2k, 2k + 1)`` (``rope_interleave``)
    becomes one that rotates halves ``(k, k + d/2)``, by moving the even
    values to the first half and the odd ones to the second. Applied once,
    where weights are made or loaded, to the output columns of the
    projections whose values are rotated: a rotation over a PART of a head
    (latent attention's 64 rope values beside 128 that are not turned) is
    ``apply_rope`` over that part alone, and a key all heads share is one
    head. A dot product of two vectors permuted alike is unchanged, so
    ``q_pe . k_pe`` is the source's."""
    return jnp.concatenate([w[..., 0::2], w[..., 1::2]], axis=-1)
