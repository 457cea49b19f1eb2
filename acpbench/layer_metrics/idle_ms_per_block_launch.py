"""Scheduler: milliseconds a decode block in which the chip sat idle while
the engine thread was in `acp.launch` (preparing a program's inputs and making its jitted call), from the
program's spans on the trace's clock (host_spans.py)."""

from .. import host_spans


def read(run):
    return host_spans.idle_ms_per_block(run, "launch")
