"""Kernels: the least time the chip could take over the rows the walks must
fetch and attend (kernels/sparse_walk.py: chosen rows x K and V, bound by
bytes) over the device time of the leaf `sparse_walk` in decode steps,
whatever implements it, in %. A program without the leaf gives None."""

from functools import partial

from ..kernels import sparse_walk
from . import _sparse


def read(run):
    c = run.config
    if "sa_config" not in c:
        return None
    sizes = {"topk": c["sa_config"]["topk"], "n_layers": c["num_hidden_layers"], "head_dim": c["head_dim"]}
    return _sparse.roofline(
        run, "sparse_walk", partial(sparse_walk.bytes_per_step, kv_heads=c["num_key_value_heads"], **sizes),
        partial(sparse_walk.flops_per_step, heads=c["num_attention_heads"], **sizes))
