"""Scheduler: milliseconds a decode block in which the chip sat idle while
the engine thread was in `acp.admit` (draining the queue, collecting groups, allocating pages, planning the cycle), from the
program's spans on the trace's clock (host_spans.py)."""

from .. import host_spans


def read(run):
    return host_spans.idle_ms_per_block(run, "admit")
