"""Kernels: the least time the chip could take for what the prefills' chunked
Mamba-2 scans must move and do (kernels/ssd.py: the greater of `scan_bytes`
over the HBM bandwidth and `scan_flops` over the bf16 peak; real tokens and
rows a call from the `ssm.prefill` counters over the traced slice, times the
`ssm_scan` calls the trace holds) over that kernel's device time, in %."""

from .. import peaks
from ..kernels import ssd
from ._ssd import sizes
from ._ssm import kernel_events, per_call


def read(run):
    tokens, rows = per_call(run, "prefill", "tokens"), per_call(run, "prefill", "rows")
    found, dims = kernel_events(run, r"ssm_scan"), sizes(run)
    if tokens is None or rows is None or not found or dims is None:
        return None
    calls, seconds = found
    least = ssd.least_seconds(ssd.scan_bytes(tokens * calls, rows * calls, **dims),
                              ssd.scan_flops(tokens * calls, chunk=run.config.get("chunk_size", 128), **dims),
                              peaks.peaks(run.device_kind))
    return 100.0 * least / seconds
