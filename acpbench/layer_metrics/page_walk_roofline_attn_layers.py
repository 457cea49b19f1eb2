"""Kernels: `page_walk_roofline` for a model whose pool holds its attention
layers alone: the walks a step are the configuration's `full_attention`
layers, and the head width is the file's `head_dim` (kernels/page_walk.py
as it is)."""

from .. import peaks, trace_reduce
from ..kernels import page_walk
from ._common import decode_steps_traced, traced_window
from .page_walk_roofline import SAMPLES, live_lengths


def read(run):
    c = run.config
    if "layer_types" not in c or "head_dim" not in c:
        return None
    steps = decode_steps_traced(run)
    kernel_s = trace_reduce.seconds_of(run.trace, "ops", r"page_walk") if steps else 0.0
    if not steps or not kernel_s:
        return None
    t0, t1 = traced_window(run)
    per_step = [
        page_walk.bytes_per_step(
            live_lengths(run, t0 + (t1 - t0) * (i + 0.5) / SAMPLES), page_size=c["engine"]["page_size"],
            kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            n_layers=sum(t == "full_attention" for t in c["layer_types"]))
        for i in range(SAMPLES)
    ]
    least_s = sum(per_step) / SAMPLES * steps / peaks.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
