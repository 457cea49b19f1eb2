"""The selective state-space recurrence's two kernels (`ssm_update`,
`ssm_scan`): the bytes each must move, from counts.

Both are bound by bytes against the table of peaks, which has no vector-unit
peak: an element of state costs one exponential and about seven multiplies
and adds a token, against 8 bytes moved once a decode step (so the update
sits near its bytes' time) or once a ROW of a prefill (so the scan, which
keeps `h` in VMEM across a row's tokens, sits far under it: its bound is the
vector unit's, about `d_state * d_inner * 8` operations a token and layer).

`update_bytes`: a decode lane of one Mamba layer reads and writes its state
(`d_state x d_inner` float32 each way), reads the step's `delta` and `u'`
rows and writes its `y` row (`d_inner` float32 each), and reads `B` and `C`
(`d_state` float32 each). The conv's three columns, `z` and the projections
are other ops' and are not counted: the share is of this kernel's time.

`scan_bytes`: a real token of one Mamba layer reads `delta` and `u'` and
writes `y` (`d_inner` float32 each) and reads `B` and `C`; a row reads the
state it starts from and writes the state at its end and at its snapshot
(`d_state x d_inner` float32 each). Padding tokens and the chunks skipped
past a row's end are the kernel's own cost and no part of the least.
"""

from __future__ import annotations

F32 = 4


def update_bytes(lanes: float, *, d_inner: int, d_state: int) -> float:
    """`lanes`: decode lanes updated, summed over Mamba layers."""
    return lanes * (2 * d_state * d_inner + 3 * d_inner + 2 * d_state) * F32


def scan_bytes(tokens: float, rows: float, *, d_inner: int, d_state: int) -> float:
    """`tokens`, `rows`: real tokens and rows scanned, summed over Mamba layers."""
    return (tokens * (3 * d_inner + 2 * d_state) + rows * 3 * d_state * d_inner) * F32


def scan_flops(tokens: float, *, d_inner: int, d_state: int) -> float:
    """Vector operations of the recurrence (no peak to hold them to): an
    exponential, four multiplies, two adds and the sum over n an element."""
    return tokens * 8 * d_state * d_inner


def least_seconds(n_bytes: float, peaks: dict) -> float:
    return n_bytes / peaks["hbm_bytes_per_s"]
