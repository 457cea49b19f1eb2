"""Shared by the readers of latent attention at two ranks (`models/dots.py`):
device seconds of decode-block runs by the leaves the program opens where a
full layer scores, chooses and walks its chosen latent rows (`index_proj`,
`index_scores`, `index_select`, `sparse_latent`), where a sliding layer walks
its ring (`ring_latent`) and where both absorb and gate (`mla_absorb`,
`attn_gate`), found by path as `_loops.by_leaf` finds its own; the least time
a step's leaf could take (live lengths sampled as `page_walk_roofline`
samples them); and of the PREFILL runs, the seconds in which a full layer's
mask is made and attended under (`_sparse.py`'s rule for which prompts a
slice holds). A configuration without the two latent ranks and the indexer's
keys (`index_topk`, `swa_kv_lora_rank`), a run without a trace, or a program
without the scopes gives None."""

from __future__ import annotations

from .. import device_scopes, host_spans, peaks, trace_reduce
from . import _sparse
from ._common import decode_steps_traced, traced_window
from ._loops import by_leaf
from .page_walk_roofline import SAMPLES, live_lengths

LEAVES = ("index_proj", "index_scores", "index_select", "sparse_latent", "ring_latent", "mla_absorb", "attn_gate")
MASK_LEAVES = ("index_scores", "index_select", "sparse_mask")  # a prefill's: scoring, the threshold, the rest of the mask
KERNEL = r"masked_prefill_attention"


def serves(run) -> bool:
    return run.trace is not None and "index_topk" in run.config and "swa_kv_lora_rank" in run.config


def layers(config: dict, kind: str) -> int:
    return sum(t == kind for t in config["layer_types"])


def leaf_seconds(run):
    """Once a run: (decode steps of the slice, `by_leaf` of it), kept on the
    run, and a `[dots]` line of ms a step by leaf; the prefill runs' mask
    leaves beside it (`run.dots_mask`). None where nothing is to be read."""
    if not serves(run):
        return None
    if not hasattr(run, "dots_leaves"):
        steps = decode_steps_traced(run)
        path = host_spans.find(run) if steps else None
        found = mask = None
        if path:
            import jax

            runs, tables = device_scopes.module_runs(jax.profiler.ProfileData.from_file(path)), device_scopes.op_table(path)
            found = by_leaf(run.trace["op_intervals"], runs, tables, LEAVES)
            mask = by_leaf(run.trace["op_intervals"], _sparse.prefills_as_decode(runs), tables, MASK_LEAVES)
            found, mask = (found if any(found.values()) else None), (mask if any(mask.values()) else None)
            run.sparse_prefill_ends = _sparse.prefill_ends(run, runs)  # what `_sparse.prompt_lengths` goes by
        if found:
            print("[dots] decode ms a step by leaf "
                  + " ".join(f"{name}={s * 1e3 / steps:.4f}" for name, s in found.items())
                  + f" over {steps:g} steps", flush=True)
        run.dots_leaves = (steps, found) if found else None
        run.dots_mask = mask
    return run.dots_leaves


def ms_per_step(run, *leaves: str):
    got = leaf_seconds(run)
    if not got or not all(got[1].get(leaf) for leaf in leaves):
        return None
    return sum(got[1][leaf] for leaf in leaves) * 1e3 / got[0]


def roofline(run, leaf: str, bytes_per_step, flops_per_step):
    """The least time over the leaf's device time, in %: the greater of the
    bytes over the HBM peak and the operations over the bf16 peak, a step,
    times the slice's steps."""
    got = leaf_seconds(run)
    if not got or not got[1].get(leaf):
        return None
    steps, found = got
    t0, t1 = traced_window(run)
    peak = peaks.peaks(run.device_kind)
    least = []
    for i in range(SAMPLES):
        lens = live_lengths(run, t0 + (t1 - t0) * (i + 0.5) / SAMPLES)
        least.append(max(bytes_per_step(lens) / peak["hbm_bytes_per_s"], flops_per_step(lens) / peak["bf16_flops"]))
    return 100.0 * sum(least) / SAMPLES * steps / found[leaf]


def mask_prefill_ms_per_ktok(run):
    """Device ms in which the slice's prefill runs make the full layers'
    masks and attend under them (the kernel), a 1,000 prompt tokens; a
    `[dots]` line of the parts."""
    if not serves(run):
        return None
    leaf_seconds(run)
    mask, kernel = getattr(run, "dots_mask", None), trace_reduce.seconds_of(run.trace, "ops", KERNEL)
    lengths = _sparse.prompt_lengths(run)
    if not mask or not kernel or not lengths:
        return None
    ktok = sum(lengths) / 1e3
    if not hasattr(run, "dots_prefill_said"):
        run.dots_prefill_said = True
        print("[dots] prefill ms a 1,000 prompt tokens " + " ".join(f"{n}={s * 1e3 / ktok:.4f}" for n, s in mask.items())
              + f" {KERNEL}={kernel * 1e3 / ktok:.4f} over {ktok:g} thousand", flush=True)
    return (sum(mask.values()) + kernel) * 1e3 / ktok
