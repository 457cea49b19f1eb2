"""KV manager: seconds in `Engine.__init__`, the `init` set-up phase: the
pool's init program and the device filling it (`init.pool`, on the `[setup]`
line), the programs wrapped in their jits, weights where the engine makes
them (acpbench/setup_phases.py)."""

from .. import setup_phases


def read(run):
    return setup_phases.value(run, "init_s")
