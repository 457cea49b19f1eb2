"""Shared by the sparse-attention readers: device seconds of decode-block
runs by the leaves the program opens where it scores, chooses and walks
(`index_proj`, `index_scores`, `index_select`, `sparse_walk`, found by path
as `_loops.by_leaf` finds its own: one pass over the slice's op intervals),
and the least time a step's leaf could take (live lengths sampled as
`page_walk_roofline` samples them); and of the PREFILL runs, the seconds in
which a prompt's mask is made (`index_scores`, `index_select` and the rest
of `sparse_mask` there) and those of the kernel that attends under it
(`masked_prefill_attention`), over the prompt tokens of the requests whose
prefill RAN in the slice (`prompt_lengths`: a 6 s slice holds two to four
prefills of a second each, so a prompt counted by when its first token was
handed over, as `prefill_ms_per_ktok` counts them, while its prefill ran
before the slice began reads a half or twice: my chip runs, PR 59). A
configuration without `sa_config`, a run without a trace, or a program
without the scopes gives None."""

from __future__ import annotations

from .. import device_scopes, host_spans, metrics, peaks, trace_reduce
from ._common import decode_steps_traced, traced_window
from ._loops import by_leaf
from .page_walk_roofline import SAMPLES, live_lengths

SLACK_S = 0.01  # the device planes lag the host's clock by 1-3 ms in a trace (PERF.md, PR 26)
LEAVES = ("index_proj", "index_scores", "index_select", "sparse_walk")
MASK_LEAVES = ("index_scores", "index_select", "sparse_mask")  # a prefill's: scoring, the threshold, the rest of the mask
KERNEL = r"masked_prefill_attention"


def prefills_as_decode(runs):
    """`by_leaf` sums the ops of decode-block runs alone (`_loops.py`, not
    this PR's to edit): the slice's prefill and continuation runs handed to
    it under the decode block's name, every other run under none."""
    name = {"prefill": "decode_block", "decode": "other", "other": "other"}
    return [(plane, [(s, e, name[device_scopes.phase_of(module)], program) for s, e, module, program in chip_runs])
            for plane, chip_runs in runs]


def leaf_seconds(run):
    """Once a run: (decode steps of the slice, `by_leaf` of it), kept on the
    run, and a `[sparse]` line of ms a step by leaf; the prefill runs' mask
    leaves beside it (`run.sparse_mask`). None where nothing is to be read."""
    if "sa_config" not in run.config or run.trace is None:
        return None
    if not hasattr(run, "sparse_leaves"):
        steps = decode_steps_traced(run)
        path = host_spans.find(run) if steps else None
        found = mask = None
        if path:
            import jax

            runs, tables = device_scopes.module_runs(jax.profiler.ProfileData.from_file(path)), device_scopes.op_table(path)
            found = by_leaf(run.trace["op_intervals"], runs, tables, LEAVES)
            mask = by_leaf(run.trace["op_intervals"], prefills_as_decode(runs), tables, MASK_LEAVES)
            found, mask = (found if any(found.values()) else None), (mask if any(mask.values()) else None)
            run.sparse_prefill_ends = prefill_ends(run, runs)
        if found:
            print("[sparse] decode ms a step by leaf "
                  + " ".join(f"{name}={s * 1e3 / steps:.4f}" for name, s in found.items())
                  + f" over {steps:g} steps", flush=True)
        run.sparse_leaves = (steps, found) if found else None
        run.sparse_mask = mask
    return run.sparse_leaves


def prefill_ends(run, runs):
    """When each whole prefill run of the first chip's reduced window ended, on the benchmark's clock."""
    window = traced_window(run)
    if window is None or not runs or not run.trace.get("windows"):
        return []
    w0, w1 = run.trace["windows"][0]
    return sorted(window[0] + (end - w0) / 1e9 for start, end, module, _program in runs[0][1]
                  if device_scopes.phase_of(module) == "prefill" and start >= w0 and end <= w1)


def prefilled(run):
    """[(prompt length, seconds from its prefill run's end to its first
    token)] of the requests whose prefill ran inside the slice: a prefill run
    (one prompt a dispatch) is the request whose first token was handed over
    next after the run ended, runs and first tokens taken in their order.
    None where the trace gives no run to go by or a run finds no request."""
    ends = getattr(run, "sparse_prefill_ends", None) or ()
    firsts = sorted((r.first_t, r.prompt_len) for r in run.records if r.first_t is not None)
    found, at = [], 0
    for end in ends:
        while at < len(firsts) and firsts[at][0] < end - SLACK_S:
            at += 1
        if at < len(firsts):
            found.append((firsts[at][1], firsts[at][0] - end))
            at += 1
    return found if found and len(found) == len(ends) else None


def prompt_lengths(run):
    """The prompts whose prefill ran inside the slice (`prefilled`), else
    those whose first token came inside the traced window, as
    `prefill_ms_per_ktok` counts them."""
    window = traced_window(run)
    if window is None:
        return []
    found = prefilled(run)
    if found:
        return [n for n, _gap in found]
    return [r.prompt_len for r in run.records if metrics.in_window(r.first_t, window)]


def prefill_seconds(run):
    """(seconds making the prompts' masks, seconds of the kernel that attends
    under them) in the slice's prefill runs, or None; a `[sparse]` line."""
    if "sa_config" not in run.config or run.trace is None:
        return None
    leaf_seconds(run)
    mask, kernel = getattr(run, "sparse_mask", None), trace_reduce.seconds_of(run.trace, "ops", KERNEL)
    if not mask or not kernel or not prompt_lengths(run):
        return None
    if not hasattr(run, "sparse_prefill_said"):
        run.sparse_prefill_said = True
        ktok = sum(prompt_lengths(run)) / 1e3
        print("[sparse] prefill ms a 1,000 prompt tokens " + " ".join(f"{n}={s * 1e3 / ktok:.4f}" for n, s in mask.items())
              + f" {KERNEL}={kernel * 1e3 / ktok:.4f} over {ktok:g} thousand"
              + (f"; the slice's whole prefill runs, [prompt tokens, ms from the run's end to the first token] "
                 f"{[[n, round(gap * 1e3, 1)] for n, gap in prefilled(run)]}" if prefilled(run) else
                 "; prompts counted by their first token's time (no whole prefill run paired)"), flush=True)
    return sum(mask.values()), kernel


def prefill_ms_per_ktok(run, part: int):
    got = prefill_seconds(run)
    return None if got is None else got[part] * 1e3 / (sum(prompt_lengths(run)) / 1e3)


def ms_per_step(run, leaf: str):
    got = leaf_seconds(run)
    if not got or not got[1].get(leaf):
        return None
    return got[1][leaf] * 1e3 / got[0]


def roofline(run, leaf: str, bytes_per_step, flops_per_step):
    """The least time over the leaf's device time, in %: the greater of the
    bytes over the HBM peak and the operations over the bf16 peak, a step,
    times the slice's steps. `bytes_per_step(lens)` and `flops_per_step(lens)`
    count one decode step at live lengths `lens`."""
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
