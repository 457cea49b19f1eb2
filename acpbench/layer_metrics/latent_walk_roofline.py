"""Kernels: the least time the chip could take over the latent rows the
walks must cover (kernels/latent_walk.py: the greater of the rows' bytes,
one read a page, over the HBM peak, and of the two products' operations over
the bf16 peak; live slots sampled as `page_walk_roofline` samples them) over
the `paged_latent_walk` kernel's device time in decode steps, in %. A
program without the kernel gives None."""

from .. import peaks, trace_reduce
from ..kernels import latent_walk
from ._common import decode_steps_traced, traced_window
from .page_walk_roofline import SAMPLES, live_lengths

KERNEL = r"latent_walk"


def read(run):
    c = run.config
    if "kv_lora_rank" not in c or run.trace is None:
        return None
    steps = decode_steps_traced(run)
    kernel_s = trace_reduce.seconds_of(run.trace, "ops", KERNEL) if steps else 0.0
    if not steps or not kernel_s:
        return None
    t0, t1 = traced_window(run)
    row, latent, layers = c["kv_lora_rank"] + c["qk_rope_head_dim"], c["kv_lora_rank"], c["num_hidden_layers"]
    peak = peaks.peaks(run.device_kind)
    least = []
    for i in range(SAMPLES):
        lens = live_lengths(run, t0 + (t1 - t0) * (i + 0.5) / SAMPLES)
        by_bytes = latent_walk.bytes_per_step(lens, page_size=c["engine"]["page_size"], row_values=row,
                                              n_layers=layers) / peak["hbm_bytes_per_s"]
        by_ops = latent_walk.flops_per_step(lens, heads=c["num_attention_heads"], row_values=row,
                                            latent_values=latent, n_layers=layers) / peak["bf16_flops"]
        least.append(max(by_bytes, by_ops))
    return 100.0 * sum(least) / SAMPLES * steps / kernel_s
