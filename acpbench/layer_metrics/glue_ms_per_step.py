"""Programs: device ms a decode step, under `acp.attn`, `acp.mixer` and
`acp.ffn`, of ops that are neither a Pallas kernel nor a matmul fusion:
norms, ropes, sorts, selects, copies, relayouts (device_scopes.py)."""

from .. import device_scopes


def read(run):
    return device_scopes.glue_ms_per_step(run)
