"""Shared by the per-layer readers: counter deltas and trace reductions."""

from __future__ import annotations

from .. import trace_reduce

DECODE = r"decode_block"
PREFILL = r"prefill|continue"


def delta(run, *path, edges=("open", "close")):
    """A counter of `Engine.stats()` at the window's close less its open."""
    if not all(e in run.stats for e in edges):
        return None

    def at(snap):
        for key in path:
            if not isinstance(snap, dict) or key not in snap:
                return None
            snap = snap[key]
        return snap

    a, b = at(run.stats[edges[0]]), at(run.stats[edges[1]])
    return None if a is None or b is None else b - a


def decode_steps_traced(run):
    """Decode steps the device ran in the traced window: runs of the decode
    program times the steps of a block."""
    if run.trace is None:
        return None
    block = run.stats["open"].get("decode_block_size", 8)
    return trace_reduce.runs_of(run.trace, DECODE) * block


def decode_step_ms(run):
    steps = decode_steps_traced(run)
    if not steps:
        return None
    return trace_reduce.seconds_of(run.trace, "modules", DECODE) / steps * 1e3


def traced_window(run):
    """What the reduced trace covers, on the benchmark's clock: the whole
    program runs of the slice, counted from when the profiler was started
    (the first op it saw came within milliseconds of that)."""
    if run.trace is None or run.traced is None:
        return None
    return tuple(run.traced[0] + s for s in run.trace["slice_s"])
