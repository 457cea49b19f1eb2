"""Kernels: the least time the chip could take to read the pages the window
layers' walks must read (kernels/window_walk.py: `min(len, window)` rows a
live slot rounded up to pages, K and V, the file's `sliding_attention`
layers, over the live slots sampled as `page_walk_roofline` samples them)
over the `paged_window_walk` kernel's device time in decode steps, in %. A
program without the kernel gives None."""

from .. import peaks, trace_reduce
from ..kernels import window_walk
from ._common import decode_steps_traced, traced_window
from .page_walk_roofline import SAMPLES, live_lengths

KERNEL = r"window_walk"


def read(run):
    c = run.config
    if "sliding_window" not in c or "layer_types" not in c:
        return None
    steps = decode_steps_traced(run)
    kernel_s = trace_reduce.seconds_of(run.trace, "ops", KERNEL) if steps else 0.0
    if not steps or not kernel_s:
        return None
    t0, t1 = traced_window(run)
    per_step = [
        window_walk.bytes_per_step(
            live_lengths(run, t0 + (t1 - t0) * (i + 0.5) / SAMPLES), window=c["sliding_window"],
            page_size=c["engine"]["page_size"], kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            n_layers=sum(t == "sliding_attention" for t in c["layer_types"]))
        for i in range(SAMPLES)
    ]
    least_s = sum(per_step) / SAMPLES * steps / peaks.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
