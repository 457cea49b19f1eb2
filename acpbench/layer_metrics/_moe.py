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


def per_decode_step(run, counter: str, edges=TRACED):
    """A `stats()["moe"]["decode"]` counter over the decode steps between
    two snapshots."""
    n, steps = delta(run, "moe", "decode", counter, edges=edges), delta(run, "decode_steps", edges=edges)
    if n is None or not steps:
        return None
    return n / steps


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
