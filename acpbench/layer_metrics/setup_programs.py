"""Scheduler: program keys first dispatched before the window opened: every
one is traced, lowered and compiled or loaded in every process
(acpbench/setup_phases.py)."""

from .. import setup_phases


def read(run):
    return setup_phases.value(run, "programs")
