"""Programs: seconds of set-up spent retrieving executables from the
persistent cache where it hit: the sum of `load_ms`
(acpbench/setup_phases.py). 0 in a cold run."""

from .. import setup_phases


def read(run):
    return setup_phases.value(run, "load_s")
