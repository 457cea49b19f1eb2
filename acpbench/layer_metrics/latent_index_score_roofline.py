"""Kernels: the least time the chip could take to score every cached row of
the full layers (kernels/index_scores.py at this configuration's sizes:
context rows x the indexer's key of 128 values, or the 64 x 128 products a
row, whichever is greater) over the device time of the leaf `index_scores` in
decode steps, whatever implements it, in %. A program without the leaf gives
None."""

from functools import partial

from ..kernels import index_scores
from . import _dots


def read(run):
    if not _dots.serves(run):
        return None
    c = run.config
    sizes = {"index_head_dim": c["index_head_dim"], "n_layers": _dots.layers(c, "full_attention")}
    return _dots.roofline(run, "index_scores", partial(index_scores.bytes_per_step, **sizes),
                          partial(index_scores.flops_per_step, index_heads=c["index_n_heads"], **sizes))
