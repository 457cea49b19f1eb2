"""Programs: device time a decode step of the leaf `sparse_walk`
(the fetch of the chosen rows of K and V and the attention over them),
over all layers, in ms: the ops of the decode-block runs whose path holds the
scope (`_sparse.leaf_seconds`). A program without the leaf gives None."""

from . import _sparse


def read(run):
    return _sparse.ms_per_step(run, "sparse_walk")
