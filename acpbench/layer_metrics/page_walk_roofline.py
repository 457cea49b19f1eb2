"""Kernels: the least time the chip could take to read the live KV pages
the walks must read (kernels/page_walk.py, bound by bytes) over the page
walk kernel's device time, in %."""

from .. import peaks, trace_reduce
from ..kernels import page_walk
from ._common import decode_steps_traced, traced_window

SAMPLES = 24


def live_lengths(run, t: float) -> list[int]:
    """Context length of every request decoding at time t."""
    out = []
    for r in run.records:
        if r.first_t is None or not (r.first_t <= t <= (r.last_t or r.first_t)):
            continue
        out.append(r.prompt_len + sum(n for at, n in r.blocks if at <= t))
    return out


def read(run):
    steps = decode_steps_traced(run)
    if not steps:
        return None
    kernel_s = trace_reduce.seconds_of(run.trace, "ops", r"page_walk")
    if not kernel_s:
        return None
    c, tp = run.config, run.config["engine"].get("tensor_parallelism", 1)
    t0, t1 = traced_window(run)
    per_step = [
        page_walk.bytes_per_step(
            live_lengths(run, t0 + (t1 - t0) * (i + 0.5) / SAMPLES),
            page_size=c["engine"]["page_size"], kv_heads=c["num_key_value_heads"] // tp,
            head_dim=c["hidden_size"] // c["num_attention_heads"], n_layers=c["num_hidden_layers"])
        for i in range(SAMPLES)
    ]
    least_s = sum(per_step) / SAMPLES * steps / peaks.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
