"""Shared by the expert-layer readers: the `moe` counters of
`Engine.stats()` a decode step, and the expert layers' device time inside
the decode program from the reduced trace's op intervals.

The profiler records an op under its HLO name and output shape, and no
scope: a fusion traced under `moe_route` is `fusion.N`. What can be told
apart is what the layer's structure gives (`ops/moe.py`): each expert layer
of a decode step begins with the router's product, the first op whose
output is `f32[lanes, num_experts]`, and ends with its second `moe_gmm`
kernel (gate-and-up, then down). Decode's ops are told from a prefill's by
their rows: a decode step routes at most `max_slots` lanes, a prefill a
bucket of tokens. A program without these ops or counters (a parent commit)
gives None, and the line leaves the metric out.
"""

from __future__ import annotations

import re

from ._common import delta

TRACED = ("trace_start", "trace_stop")
KERNEL = re.compile(r"%?moe_gmm[.\d]* = \w+\[(\d+),\d+\]")
OUT = re.compile(r"= f32\[(\d+),(\d+)\]")


def per_decode_step(run, counter: str):
    """A `stats()["moe"]["decode"]` counter a decode step, over the traced
    slice. Taken an expert layer first, counter and layers from the
    device's one copy, then times the expert layers a step: a whole number,
    so the window's edges give it exactly. Over the host's `decode_steps`
    of the slice it read up to a block out of step with the device's
    counters, a seventh of a 2 s slice (`moe_gmm_roofline` 99.4% beside
    85.0% on one seed; my chip runs, PR 32)."""
    n = delta(run, "moe", "decode", counter, edges=TRACED)
    layers = delta(run, "moe", "decode", "expert_layers", edges=TRACED)
    in_window, steps = delta(run, "moe", "decode", "expert_layers"), delta(run, "decode_steps")
    if n is None or not layers or not in_window or not steps:
        return None
    return n / layers * round(in_window / steps)


def decode_expert_seconds(run):
    """(seconds in decode steps' `moe_gmm` kernels, seconds from each
    decode expert layer's router product to the end of its second grouped
    matmul), mean over chips; None without a trace or without the kernel."""
    if run.trace is None:
        return None
    c, moe = run.config, run.stats.get("open", {}).get("moe")
    if moe is None:
        return None
    lanes = run.stats["open"]["max_slots"]
    rows_max = lanes * moe["experts_per_token"] + 16 * (moe["held"] + 1)
    kernel = layer = 0.0
    for ops in run.trace["op_intervals"]:
        began, seen = None, 0
        for s, e, name in ops:
            k = KERNEL.match(name)
            if k:
                if int(k.group(1)) > rows_max:
                    began, seen = None, 0  # a prefill's
                    continue
                kernel += e - s
                seen += 1
                if seen == 2 and began is not None:
                    layer += e - began
                if seen == 2:
                    began, seen = None, 0
                continue
            o = OUT.search(name)
            if o and began is None and int(o.group(2)) == c["num_experts"] and int(o.group(1)) <= lanes:
                began, seen = s, 0
    chips = max(1, len(run.trace["op_intervals"]))
    if not kernel:
        return None
    return kernel / 1e9 / chips, layer / 1e9 / chips
