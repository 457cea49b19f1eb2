"""Programs: device time of the latent walks (`paged_latent_walk`) a decode
step, in ms, over the traced slice's decode steps. A program without the
kernel gives None."""

from .. import trace_reduce
from ._common import decode_steps_traced
from .latent_walk_roofline import KERNEL


def read(run):
    if run.trace is None:
        return None
    steps = decode_steps_traced(run)
    kernel_s = trace_reduce.seconds_of(run.trace, "ops", KERNEL) if steps else 0.0
    if not steps or not kernel_s:
        return None
    return kernel_s * 1e3 / steps
