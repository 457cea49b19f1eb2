"""The Mamba-2 recurrence's two kernels (the decode update and the chunked
prefill scan; the program names their Pallas calls `ssm_update` and
`ssm_scan`, the names `device_scopes.KERNELS` files): what each must move and
do, from counts, whatever implements it. `kernels/ssm.py` is Mamba-1's: a
state `d_state x d_inner` with a decay an element; here the state is `heads
x head_dim x d_state` float32 (4 MiB a slot and layer at the published
sizes), the decay one scalar a head, `B` and `C` a group's.

`update_bytes`: a decode lane of one Mamba layer reads and writes its state
(`heads x head_dim x d_state` float32 each way), reads the step's `x` and
writes its `y` (`heads x head_dim` float32 each), reads `dt` (`heads`) and
`B` and `C` (`n_groups x d_state` each). The conv's columns, `z`, the gated
norm and the projections are other ops' and are not counted. Bound by bytes:
an element of state costs two multiplies and two adds a step against 8 bytes
moved.

`scan_bytes`: a real token of one Mamba layer reads `x` and writes `y`
(`heads x head_dim` float32 each) and reads `dt`, `B` and `C`; a row reads
the state it starts from and writes the state at its end and at its
snapshot. `scan_flops`: the chunked form's matrix products a token, the
causal half of a chunk counted (the masked half is the kernel's own cost):
`C B^T` of its group against the `chunk / 2` tokens before it, that product
against their `x`, `S_in C` and `x (x) B`. The share takes the greater of
bytes over the HBM bandwidth and operations over the bf16 peak (the table
has no float32 peak: the kernel's products run at float32 contract
precision, several bf16 passes, so it reads under its bytes' share by
nature). Padding tokens and chunks skipped past a row's end are no part of
the least.
"""

from __future__ import annotations

F32 = 4


def update_bytes(lanes: float, *, heads: int, head_dim: int, d_state: int, n_groups: int) -> float:
    """`lanes`: decode lanes updated, summed over Mamba layers."""
    return lanes * (2 * heads * head_dim * d_state + 2 * heads * head_dim + heads + 2 * n_groups * d_state) * F32


def scan_bytes(tokens: float, rows: float, *, heads: int, head_dim: int, d_state: int, n_groups: int) -> float:
    """`tokens`, `rows`: real tokens and rows scanned, summed over Mamba layers."""
    per_token = 2 * heads * head_dim + heads + 2 * n_groups * d_state
    return (tokens * per_token + rows * 3 * heads * head_dim * d_state) * F32


def scan_flops(tokens: float, *, heads: int, head_dim: int, d_state: int, n_groups: int, chunk: int = 128) -> float:
    per_token = (2 * n_groups * d_state * (chunk // 2)  # C_t . B_s over the tokens of the chunk at or before it
                 + 2 * heads * head_dim * (chunk // 2)  # that product, decayed, against their x
                 + 2 * heads * head_dim * d_state  # S_in C_t
                 + 2 * heads * head_dim * d_state)  # x_t (x) B_t into the chunk's state
    return tokens * per_token


def least_seconds(n_bytes: float, flops: float, peaks: dict) -> float:
    return max(n_bytes / peaks["hbm_bytes_per_s"], flops / peaks["bf16_flops"])
