"""Programs: device time of the recurrence a decode step, in ms: the
`ssm_update` kernel's calls and the conv-column writes that precede them
(_ssm.py says how those are found), over the decode steps of the traced
slice."""

from ._common import decode_steps_traced
from ._ssm import decode_conv_seconds, kernel_events


def read(run):
    found, steps = kernel_events(run, r"ssm_update"), decode_steps_traced(run)
    if not found or not steps:
        return None
    return (found[1] + decode_conv_seconds(run)) * 1e3 / steps
