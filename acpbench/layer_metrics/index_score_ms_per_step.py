"""Programs: device time a decode step of the leaf `index_scores`
(the gather of a lane's indexer keys and its scores of every cached row),
over all layers, in ms: the ops of the decode-block runs whose path holds the
scope (`_sparse.leaf_seconds`). A program without the leaf gives None."""

from . import _sparse


def read(run):
    return _sparse.ms_per_step(run, "index_scores")
