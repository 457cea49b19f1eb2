"""Shared by the looped family's readers: the walk's seconds, and device
seconds of decode-block runs by the program's leaf scopes, one pass over the
slice's op intervals (the join is `device_scopes.attribute`'s: program id
and event name). A configuration without `total_ut_steps`, a run without a
trace, or a program without the kernel or the scopes gives None."""

from __future__ import annotations

import bisect

from .. import device_scopes, host_spans, trace_reduce
from ._common import decode_steps_traced

KERNEL = r"page_walk"
MATMUL_LEAVES = ("attn_qkv", "attn_out", "ffn_dense", "head_product")  # where a decode step reads its weights
LOOP_LEAVES = ("loop_norm", "exit_gate", "exit_select")  # printed beside them, read by no metric
LEAVES = MATMUL_LEAVES + LOOP_LEAVES


def looped(run) -> bool:
    return "total_ut_steps" in run.config and run.trace is not None


def walk_seconds(run):
    """(decode steps of the slice, seconds of the `paged_page_walk` kernel) or None."""
    if not looped(run):
        return None
    steps = decode_steps_traced(run)
    kernel_s = trace_reduce.seconds_of(run.trace, "ops", KERNEL) if steps else 0.0
    return (steps, kernel_s) if steps and kernel_s else None


def by_leaf(op_intervals, runs, tables, leaves=LEAVES) -> dict:
    """Seconds (mean over chips) of the ops inside decode-block runs by the
    innermost of `leaves` on the op's path (of its first path that has one,
    as `device_scopes.leaf` reads a merged instruction's)."""
    out = dict.fromkeys(leaves, 0.0)
    for ops, (plane, chip_runs) in zip(op_intervals, runs):
        table, starts = tables.get(plane, {}), [r[0] for r in chip_runs]
        spent: dict = {}
        for start, end, event in ops:
            at = bisect.bisect_right(starts, start) - 1
            if at >= 0 and start < chip_runs[at][1] and device_scopes.phase_of(chip_runs[at][2]) == "decode":
                key = (chip_runs[at][3], event)
                spent[key] = spent.get(key, 0) + end - start
        for key, ns in spent.items():
            op = table.get(key)
            on_path = ([part for part in path.split("/") if part in leaves] for path in (op.tf_op.split(";") if op else ()))
            found = next((parts[-1] for parts in on_path if parts), None)
            if found:
                out[found] += ns / 1e9 / max(1, len(op_intervals))
    return out


def leaf_seconds(run):
    """Once a run: `by_leaf` of its slice, kept on the run, and a `[loops]`
    line of ms a step by leaf. None where nothing is to be read."""
    if not looped(run):
        return None
    if not hasattr(run, "loop_leaves"):
        steps = decode_steps_traced(run)
        path = host_spans.find(run) if steps else None
        found = None
        if path:
            import jax

            runs = device_scopes.module_runs(jax.profiler.ProfileData.from_file(path))
            found = by_leaf(run.trace["op_intervals"], runs, device_scopes.op_table(path))
            if not any(found.values()):
                found = None
        if found:
            print("[loops] decode ms a step by leaf "
                  + " ".join(f"{name}={s * 1e3 / steps:.4f}" for name, s in found.items())
                  + f" over {steps:g} steps", flush=True)
        run.loop_leaves = found
    return run.loop_leaves
