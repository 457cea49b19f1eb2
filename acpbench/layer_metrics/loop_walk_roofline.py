"""Kernels: the least time the chip could take to read the live K and V
pages a looped model's walks must read (kernels/page_walk.py, bound by
bytes, with a walk a LOOP and layer: `num_hidden_layers x total_ut_steps`
cache layers; live slots sampled as `page_walk_roofline` samples them) over
the `paged_page_walk` kernel's device time in the slice's decode steps, in
%. `page_walk_roofline` counts `num_hidden_layers` walks a step and would
read a quarter of this."""

from .. import peaks
from ..kernels import page_walk
from ._common import traced_window
from ._loops import walk_seconds
from .page_walk_roofline import SAMPLES, live_lengths


def read(run):
    found = walk_seconds(run)
    if found is None:
        return None
    steps, kernel_s = found
    c = run.config
    t0, t1 = traced_window(run)
    per_step = [
        page_walk.bytes_per_step(
            live_lengths(run, t0 + (t1 - t0) * (i + 0.5) / SAMPLES), page_size=c["engine"]["page_size"],
            kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            n_layers=c["num_hidden_layers"] * c["total_ut_steps"])
        for i in range(SAMPLES)
    ]
    least_s = sum(per_step) / SAMPLES * steps / peaks.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
