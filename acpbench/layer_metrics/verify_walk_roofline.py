"""Kernels: the least time the chip could take to read what a
verify-and-draft step's walks need (kernels/verify_walk.py: each live
slot's window rows a window layer and live pages a full-attention cache
layer, the MTP block's among them, ONCE, whatever the rows a lane runs)
over the device time of both walk kernels (`paged_page_walk`,
`paged_window_walk`) in decode steps, in %. A configuration without an MTP
module, or a program without the kernels, gives None."""

from .. import peaks, trace_reduce
from ..kernels import verify_walk
from ._common import decode_steps_traced, traced_window
from .page_walk_roofline import SAMPLES, live_lengths

KERNELS = r"paged_(page|window)_walk"


def read(run):
    c = run.config
    if not c.get("num_nextn_predict_layers") or "layer_types" not in c:
        return None
    steps = decode_steps_traced(run)
    kernel_s = trace_reduce.seconds_of(run.trace, "ops", KERNELS) if steps else 0.0
    if not steps or not kernel_s:
        return None
    t0, t1 = traced_window(run)
    kinds = list(c["layer_types"])
    per_step = [
        verify_walk.bytes_per_step(
            live_lengths(run, t0 + (t1 - t0) * (i + 0.5) / SAMPLES), window=c["sliding_window"],
            page_size=c["engine"]["page_size"], kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            window_layers=kinds.count("sliding_attention"),
            full_layers=kinds.count("full_attention") + c["num_nextn_predict_layers"])
        for i in range(SAMPLES)
    ]
    least_s = sum(per_step) / SAMPLES * steps / peaks.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
