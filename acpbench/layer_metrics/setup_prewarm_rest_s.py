"""Scheduler: seconds of `Engine.prewarm()` outside the first dispatches
made in it: its bursts, the waiting for batches to form, and the freeze of
the heap (acpbench/setup_phases.py)."""

from .. import setup_phases


def read(run):
    return setup_phases.value(run, "prewarm_rest_s")
