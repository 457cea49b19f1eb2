"""Pipeline parallelism (GPipe) — the 'pp' mesh axis.

The layer stack ([L, ...] leaves, the same stacked layout the rest of the
stack scans over) shards its LEADING axis over 'pp': each rank holds L/pp
consecutive layers (one pipeline stage). The forward runs the classic
GPipe schedule inside one ``shard_map``:

- the batch splits into M microbatches;
- at step s, rank r applies its stage to microbatch ``m = s - r`` (valid
  when ``0 <= m < M``); activations rotate rank r -> r+1 between steps via
  ``lax.ppermute`` — ICI neighbor traffic, never a gather;
- bubble steps compute garbage that is never selected into an output (the
  schedule's ``where`` masks gate injection and collection), so
  correctness is exact; the cost is the usual (pp-1)/(M+pp-1) bubble.

The BACKWARD is not hand-written: ``jax.grad`` differentiates through the
schedule — the transpose of ``ppermute`` is the reverse rotation, so
autodiff yields the mirrored GPipe backward schedule automatically.
Embedding and the LM head are computed replicated outside the pipelined
stack (they are not layer-stacked leaves).

Composability: ``pipeline_forward``'s shard_map is manual over 'pp' only;
other mesh axes (dp on the batch, tp inside each stage's matmuls) stay
automatic, so GSPMD keeps partitioning them as usual (dp2 x pp2 pinned in
tests). No analogue in the reference (it runs no models); this completes
the dp/sp/tp/ep/pp axis set of the TPU data plane.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..models.llama import LlamaConfig, attn_mlp as _attn_mlp, embed as _embed, final_norm_w as _final_norm_w, head_logits as _head_logits
from ..ops.attention import causal_attention
from ..ops.norms import rms_norm
from .mesh import param_specs


def pipeline_param_specs(config: LlamaConfig) -> dict:
    """param_specs with the layer-stacked leaves' leading (layer) axis
    sharded over 'pp' (stage assignment); non-layer leaves replicated
    across pp (embed/head run on every rank)."""
    specs = param_specs(config)
    specs["layers"] = {
        k: P("pp", *spec[1:]) for k, spec in specs["layers"].items()
    }
    return specs


def pipeline_shardings(mesh, config: LlamaConfig, params_like: dict) -> dict:
    from .mesh import param_shardings

    return param_shardings(
        mesh, config, params_like, specs=pipeline_param_specs(config)
    )


def _stage_apply(local_layers: dict, x: jax.Array, positions: jax.Array,
                 config: LlamaConfig, remat: bool = False) -> jax.Array:
    """Run this rank's L/pp layers (a scan over the local slice). The
    attention-logit soft-cap (gemma-2) threads through exactly like the
    non-pipelined forward — dropping it would silently mis-train capped
    models."""

    def body(h, layer):
        out, _, _ = _attn_mlp(
            h, layer, config, positions,
            lambda q, k, v: causal_attention(
                q, k, v, positions, softcap=config.attn_logit_softcap
            ),
        )
        return out, None

    if remat:
        # same per-layer rematerialization the non-pipelined forward gets:
        # GPipe microbatching bounds the NUMBER of live microbatch
        # activations, but each stage would still save every local layer's
        # activations per microbatch without this
        body = jax.checkpoint(body, prevent_cse=False)

    out, _ = jax.lax.scan(body, x, local_layers)
    return out


def pipeline_forward(
    params: dict,
    tokens: jax.Array,  # [B, T] int32
    config: LlamaConfig,
    mesh,
    n_microbatches: int = 0,  # 0 = 2 * pp (the usual bubble/memory balance)
    remat: bool = False,
) -> jax.Array:
    """Causal forward -> logits [B, T, V] f32, layers pipelined over 'pp'."""
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    pp = axes.get("pp", 1)
    if pp <= 1:
        from ..models.llama import forward

        return forward(params, tokens, config, remat=remat)
    if config.n_layers % pp:
        raise ValueError(f"n_layers={config.n_layers} must divide over pp={pp}")
    B, T = tokens.shape
    M = n_microbatches or min(B, 2 * pp)
    if B % M:
        raise ValueError(f"batch {B} must divide into {M} microbatches")
    mb = B // M
    c = config

    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (mb, T))
    x = _embed(params, tokens, c)  # replicated compute
    xs = x.reshape(M, mb, T, c.dim)

    layer_specs = {
        k: P("pp", *([None] * (params["layers"][k].ndim - 1)))
        for k in params["layers"]
    }

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(layer_specs, P()),
        out_specs=P(),
        check_vma=False,
        # manual over 'pp' only: dp/tp stay automatic, so GSPMD keeps
        # partitioning the batch and the in-stage matmuls as usual
        axis_names=frozenset({"pp"}),
    )
    def run(local_layers, xs):
        r = jax.lax.axis_index("pp")
        perm = [(i, (i + 1) % pp) for i in range(pp)]
        cur = jnp.zeros((mb, T, c.dim), dtype=xs.dtype)
        outs = jnp.zeros((M, mb, T, c.dim), dtype=xs.dtype)
        for step in range(M + pp - 1):
            prev = jax.lax.ppermute(cur, "pp", perm)
            # rank 0 injects microbatch `step`; others take the neighbor's
            # activation. Bubble steps feed garbage that the collection
            # mask below never selects.
            inject = xs[min(step, M - 1)]
            inp = jnp.where(r == 0, inject, prev)
            m = step - r  # the microbatch THIS rank would process now
            valid = (m >= 0) & (m < M)
            cur = _stage_apply(local_layers, inp, positions, c, remat=remat)
            # rank pp-1 completes microbatch m = step - (pp - 1)
            out_m = step - (pp - 1)
            if 0 <= out_m < M:
                take = (r == pp - 1) & valid
                outs = outs.at[out_m].set(
                    jnp.where(take, cur, outs[out_m])
                )
        # replicate the collected outputs (only rank pp-1 holds them)
        outs = jax.lax.psum(
            jnp.where(r == pp - 1, outs, jnp.zeros_like(outs)), "pp"
        )
        return outs

    outs = run(params["layers"], xs)
    x = outs.reshape(B, T, c.dim)
    x = rms_norm(x, _final_norm_w(params, c), c.norm_eps)
    # _head_logits, not a bare x @ head: gemma-2's FINAL logit soft-cap
    # must apply here exactly as in the non-pipelined forward
    return _head_logits(x, params, c)


def pipeline_loss_fn(params, tokens, mask, config, mesh, n_microbatches=0,
                     remat: bool = False):
    """Next-token cross-entropy over the pipelined forward — the SAME
    objective as train.trainer.lm_loss (roll-shifted targets, last position
    masked), so pipelined and plain training are loss-comparable. Grad-able:
    autodiff through ppermute yields the GPipe backward schedule."""
    from ..train.trainer import cross_entropy_loss

    logits = pipeline_forward(params, tokens, config, mesh, n_microbatches,
                              remat=remat)
    targets = jnp.roll(tokens, -1, axis=1)
    m = mask.astype(jnp.float32).at[:, -1].set(0.0)
    return cross_entropy_loss(logits, targets, m)
