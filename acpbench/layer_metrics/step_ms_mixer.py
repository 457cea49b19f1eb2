"""Programs: device ms a decode step under `acp.mixer`, from the op
intervals of the traced slice's decode-block runs (device_scopes.py)."""

from .. import device_scopes


def read(run):
    return device_scopes.step_ms(run, "mixer")
