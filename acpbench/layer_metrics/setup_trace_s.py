"""Programs: seconds of set-up in which jax traced the programs' Python to
jaxprs: the sum of `trace_ms` over the programs' first dispatches and
`(outside)` (acpbench/setup_phases.py). A jitted function traced inside
another is counted once."""

from .. import setup_phases


def read(run):
    return setup_phases.value(run, "trace_s")
