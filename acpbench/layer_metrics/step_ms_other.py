"""Programs: device ms a decode step under `acp.embed`, `acp.commit` and no
scope at all, inside decode-block runs: with the five other `step_ms.*` it
sums to the decode-block runs' op seconds a step (device_scopes.py)."""

from .. import device_scopes


def read(run):
    return device_scopes.step_ms(run, "embed", "commit", device_scopes.UNNAMED)
