"""Scheduler: the per-request gap where it is recorded, not judged (above
the knee a cycle is a plain block or a block behind a prefill, and the mix
of the two is the scheduler's)."""

from ..end_to_end import gap_p50_ms


def read(run):
    return gap_p50_ms.read(run)
