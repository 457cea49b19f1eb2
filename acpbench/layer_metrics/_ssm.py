"""Shared by the recurrence's readers: the `ssm` counters of
`Engine.stats()` a kernel call over the traced slice, and the two kernels'
events in the reduced trace's op intervals (`ssm_update`, `ssm_scan`: the
names the program gives its Pallas calls).

A counter is taken a Mamba layer run first, counter and layers from the
device's one copy (`tokens / mamba_layers` is the tokens one kernel call
scanned, whatever block the host's snapshot cut), then times the calls the
trace holds: `_moe.per_decode_step`'s reason. The decode conv is an XLA
fusion with no name of its own: it is the op that writes the whole stack of
conv columns (`[mamba layers, slots + 1, (d_conv - 1) * d_inner]`) and is
followed, before any other such op, by an `ssm_update` call of the same
layer. The decode program calls `ssm_update` in EVERY layer of its one layer
body, at an attention layer as a pass-through of one block (`ops/pallas/
ssm_scan.py`): those calls' time is the update's cost and is counted, but
they move no lane's state, so the calls that do are `live_share` of the
trace's (`ssm_scan` runs in Mamba layers alone). A program without these
kernels or counters (a parent commit) gives None, and the line leaves the
metric out.
"""

from __future__ import annotations

import re

from ._common import delta

TRACED = ("trace_start", "trace_stop")


def sizes(run) -> dict | None:
    c = run.config
    if "mamba_d_state" not in c or "mamba_expand" not in c:
        return None
    return {"d_inner": c["mamba_expand"] * c["hidden_size"], "d_state": c["mamba_d_state"]}


def live_share(run) -> float:
    """The share of a decode step's `ssm_update` calls that step a state:
    the file's Mamba layers over all its layers."""
    kinds = run.config["layer_types"]
    return sum(t == "mamba" for t in kinds) / len(kinds)


def per_call(run, phase: str, counter: str):
    """A `stats()["ssm"][phase]` counter a kernel call, over the traced slice."""
    n = delta(run, "ssm", phase, counter, edges=TRACED)
    layers = delta(run, "ssm", phase, "mamba_layers", edges=TRACED)
    if n is None or not layers:
        return None
    return n / layers


def kernel_events(run, name: str):
    """(calls, seconds) of the kernel `name`, mean over chips; None without
    a trace or without the kernel."""
    if run.trace is None:
        return None
    rx = re.compile(name)
    calls, ns = 0, 0
    for ops in run.trace["op_intervals"]:
        for s, e, op in ops:
            if rx.search(op.split("=")[0]):
                calls, ns = calls + 1, ns + e - s
    chips = max(1, len(run.trace["op_intervals"]))
    return (calls / chips, ns / 1e9 / chips) if calls else None


def decode_conv_seconds(run):
    """Seconds of the decode steps' conv-column writes (module text), mean
    over chips; 0.0 where none is found."""
    c, st = run.config, run.stats.get("open", {})
    if run.trace is None or "ssm" not in st or sizes(run) is None:
        return 0.0
    n_mamba = sum(t == "mamba" for t in c["layer_types"])
    width = (c["mamba_d_conv"] - 1) * sizes(run)["d_inner"]
    shape = re.compile(rf"= \w+\[{n_mamba},{st['max_slots'] + 1},{width}\]")
    update = re.compile(r"ssm_update")
    total = 0
    for ops in run.trace["op_intervals"]:
        pending = None
        for s, e, op in ops:
            if update.search(op.split("=")[0]):
                if pending is not None:
                    total += pending
                pending = None
            elif shape.search(op):
                pending = e - s  # a later one before any update replaces it: a prefill's commit
    return total / 1e9 / max(1, len(run.trace["op_intervals"]))
