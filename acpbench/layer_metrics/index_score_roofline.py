"""Kernels: the least time the chip could take to score every cached row
(kernels/index_scores.py: context rows x the indexer's key, bound by bytes)
over the device time of the leaf `index_scores` in decode steps, whatever
implements it, in %. A program without the leaf gives None."""

from functools import partial

from ..kernels import index_scores
from . import _sparse


def read(run):
    c = run.config
    if "sa_config" not in c:
        return None
    sa = c["sa_config"]
    sizes = {"index_head_dim": sa["indexer_head_dim"], "n_layers": c["num_hidden_layers"]}
    return _sparse.roofline(run, "index_scores", partial(index_scores.bytes_per_step, **sizes),
                            partial(index_scores.flops_per_step, index_heads=sa["indexer_num_heads"], **sizes))
