"""Programs: device time of the expert layers a decode step, in ms: from
each layer's router product (under `moe_route`) through the sort to the end
of its second `moe_gmm` kernel, summed over the step's expert layers
(_moe.py says how they are found in the trace). The combine after it, a
gather of four rows a token, is left out."""

from ._common import decode_steps_traced
from ._moe import decode_expert_seconds


def read(run):
    found, steps = decode_expert_seconds(run), decode_steps_traced(run)
    if not found or not found[1] or not steps:
        return None
    return found[1] * 1e3 / steps
