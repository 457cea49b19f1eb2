"""Programs: device time of the decode programs over the decode steps in
the traced window, where tokens per second are judged."""

from ._common import decode_step_ms


def read(run):
    return decode_step_ms(run)
