"""Device: the share of the chip's idle time, in the traced slice, that
lies inside one of the engine loop's phase spans (host_spans.py), in %:
how much of the idle the program can name."""

from .. import host_spans


def read(run):
    return host_spans.idle_named_share(run)
