"""Programs: seconds of set-up the backend compiled in, where the persistent
cache missed or is off: the sum of `compile_ms` (acpbench/setup_phases.py).
Near 0 in a warm run."""

from .. import setup_phases


def read(run):
    return setup_phases.value(run, "compile_s")
