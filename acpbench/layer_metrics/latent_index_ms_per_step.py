"""Programs: device time a decode step of the full layers' indexer, score and
choice (the leaves `index_scores` and `index_select`), in ms
(`_dots.leaf_seconds`). A program without the leaves gives None."""

from . import _dots


def read(run):
    return _dots.ms_per_step(run, "index_scores", "index_select")
