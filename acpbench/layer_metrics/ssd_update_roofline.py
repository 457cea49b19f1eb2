"""Kernels: the least time the chip could take to move each live lane's
Mamba-2 state once in and once out, with its inputs, a decode step
(kernels/ssd.py `update_bytes` over the HBM bandwidth: lanes a call from the
`ssm.decode` counters over the traced slice, times the `ssm_update` calls of
the trace) over that kernel's device time, in %. Bound by bytes."""

from .. import peaks
from ..kernels import ssd
from ._ssd import sizes
from ._ssm import kernel_events, per_call


def read(run):
    lanes, found, dims = per_call(run, "decode", "rows"), kernel_events(run, r"ssm_update"), sizes(run)
    if lanes is None or not found or dims is None:
        return None
    calls, seconds = found
    return 100.0 * ssd.least_seconds(ssd.update_bytes(lanes * calls, **dims), 0.0, peaks.peaks(run.device_kind)) / seconds
