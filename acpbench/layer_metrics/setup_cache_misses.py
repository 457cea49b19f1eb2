"""Programs: programs whose first dispatch missed the persistent cache
(acpbench/setup_phases.py). A warm run reads 0; one that does not was not
warm, and its `[setup]` line names what the cache had lost."""

from .. import setup_phases


def read(run):
    return setup_phases.value(run, "cache_misses")
