"""Programs: device time a decode step of the leaf `sparse_latent` (the fetch
of the chosen latent rows and the absorbed attention over them), over the
full layers, in ms: the ops of the decode-block runs whose path holds the
scope (`_dots.leaf_seconds`). A program without the leaf gives None."""

from . import _dots


def read(run):
    return _dots.ms_per_step(run, "sparse_latent")
