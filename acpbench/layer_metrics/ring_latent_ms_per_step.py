"""Programs: device time a decode step of the leaf `ring_latent` (the sliding
layers' gather of a slot's ring of latent rows and the absorbed attention
over the window), over the sliding layers, in ms (`_dots.leaf_seconds`). A
program without the leaf gives None."""

from . import _dots


def read(run):
    return _dots.ms_per_step(run, "ring_latent")
