"""Programs: device time of the Mamba-2 update kernel (`ssm_update` calls) a
decode step, in ms, over the decode steps of the traced slice. A program of
another family (no `mamba_num_heads` in its file) gives None."""

from ._common import decode_steps_traced
from ._ssd import sizes
from ._ssm import kernel_events


def read(run):
    found, steps = kernel_events(run, r"ssm_update"), decode_steps_traced(run)
    if not found or not steps or sizes(run) is None:
        return None
    return found[1] * 1e3 / steps
