"""Programs: the same reduction, where the gap between tokens is judged."""

from ._common import decode_step_ms


def read(run):
    return decode_step_ms(run)
