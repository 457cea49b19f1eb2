"""Programs: what the programs' first dispatches took beyond jax's four
stages, their first runs on the device among it: the sum of `run_ms`
(acpbench/setup_phases.py)."""

from .. import setup_phases


def read(run):
    return setup_phases.value(run, "run_s")
