"""Shared by the Mamba-2 recurrence's readers: the file's sizes. The `ssm`
counters a kernel call and the kernels' events come from `_ssm.py` as they
are (`per_call`, `kernel_events`: the program names its Pallas calls
`ssm_update` and `ssm_scan` and counts under `stats()["ssm"]`, as the Mamba-1
family does). This family's decode step calls the update in its Mamba layers
alone, so every call of the trace steps a state. A configuration without
these keys (another family's), a program without the kernels or the counters
(a parent commit) gives None, and the line leaves the metric out.
"""

from __future__ import annotations

KEYS = {"heads": "mamba_num_heads", "head_dim": "mamba_head_dim", "d_state": "ssm_state_size", "n_groups": "n_groups"}


def sizes(run) -> dict | None:
    c = run.config
    if any(key not in c for key in KEYS.values()):
        return None
    return {name: c[key] for name, key in KEYS.items()}
