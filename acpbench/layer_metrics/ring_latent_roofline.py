"""Kernels: the least time the chip could take to read the latent rows the
sliding layers' walks must read (kernels/ring_latent.py: `min(len + 1, 513)`
rows of 1,088 values a live slot and sliding layer, bound by bytes) over the
device time of the leaf `ring_latent` in decode steps, whatever implements
it, in %. A program without the leaf gives None."""

from functools import partial

from ..kernels import ring_latent
from . import _dots


def read(run):
    if not _dots.serves(run):
        return None
    c = run.config
    sizes = {"window": c["sliding_window_size"], "row_values": c["swa_kv_lora_rank"] + c["swa_qk_rope_head_dim"],
             "n_layers": _dots.layers(c, "sliding_attention")}
    return _dots.roofline(
        run, "ring_latent", partial(ring_latent.bytes_per_step, **sizes),
        partial(ring_latent.flops_per_step, heads=c["swa_num_attention_heads"], value_width=c["swa_kv_lora_rank"], **sizes))
