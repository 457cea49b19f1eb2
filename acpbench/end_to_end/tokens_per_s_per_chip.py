"""Output tokens produced inside the window (metrics.tokens_in_window),
over its seconds and the chips: all the work and all the time of the
window."""

from .. import metrics


def read(run):
    return metrics.tokens_in_window(run.records, run.window) / run.seconds / run.chips
