"""Kernels: the least time the chip could take to read and write the live
lanes' state a decode step (kernels/ssm.py `update_bytes` over the HBM
bandwidth, lanes a call from the `ssm.decode` counters over the traced
slice, times the `ssm_update` calls of the trace that step a state:
_ssm.py `live_share`) over that kernel's device time, pass-through calls
included, in %. Bound by bytes."""

from .. import peaks
from ..kernels import ssm
from ._ssm import kernel_events, live_share, per_call, sizes


def read(run):
    lanes, found, dims = per_call(run, "decode", "rows"), kernel_events(run, r"ssm_update"), sizes(run)
    if lanes is None or not found or dims is None:
        return None
    calls, seconds = found
    least = ssm.least_seconds(ssm.update_bytes(lanes * calls * live_share(run), **dims), peaks.peaks(run.device_kind))
    return 100.0 * least / seconds
