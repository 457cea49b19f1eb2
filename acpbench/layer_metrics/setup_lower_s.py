"""Programs: seconds of set-up in which jax lowered jaxprs to MLIR modules:
the sum of `lower_ms` (acpbench/setup_phases.py)."""

from .. import setup_phases


def read(run):
    return setup_phases.value(run, "lower_s")
