"""Kernels: the least time the chip could take to move what the prefills'
chunked scans must move (kernels/ssm.py `scan_bytes` over the HBM bandwidth:
real tokens and rows a call from the `ssm.prefill` counters over the traced
slice, times the `ssm_scan` calls the trace holds) over that kernel's device
time, in %. A share of the bytes' time: the table of peaks has no
vector-unit peak, which is what bounds this kernel (kernels/ssm.py), so it
reads far under 100% by nature."""

from .. import peaks
from ..kernels import ssm
from ._ssm import kernel_events, per_call, sizes


def read(run):
    tokens, rows = per_call(run, "prefill", "tokens"), per_call(run, "prefill", "rows")
    found, dims = kernel_events(run, r"ssm_scan"), sizes(run)
    if tokens is None or rows is None or not found or dims is None:
        return None
    calls, seconds = found
    least = ssm.least_seconds(ssm.scan_bytes(tokens * calls, rows * calls, **dims), peaks.peaks(run.device_kind))
    return 100.0 * least / seconds
