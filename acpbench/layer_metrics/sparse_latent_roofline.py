"""Kernels: the least time the chip could take over the latent rows the full
layers' walks must fetch and attend in absorbed form (kernels/sparse_latent.py:
chosen rows x 576 values, or the two absorbed products' operations, whichever
is greater: the leaf stands on the chip's ridge) over the device time of the
leaf `sparse_latent` in decode steps, whatever implements it, in %. A program
without the leaf gives None."""

from functools import partial

from ..kernels import sparse_latent
from . import _dots


def read(run):
    if not _dots.serves(run):
        return None
    c = run.config
    sizes = {"topk": c["index_topk"], "row_values": c["kv_lora_rank"] + c["qk_rope_head_dim"],
             "n_layers": _dots.layers(c, "full_attention")}
    return _dots.roofline(
        run, "sparse_latent", partial(sparse_latent.bytes_per_step, **sizes),
        partial(sparse_latent.flops_per_step, heads=c["num_attention_heads"], value_width=c["kv_lora_rank"], **sizes))
