"""The grouped matmul of LATENT, UNGATED experts (`moe_gmm` over `W2_e
relu(W1_e u)^2`): the bytes it must read and the operations it must do, from
counts. `kernels/moe_gmm.py` counts three matrices of `hidden x width` an
expert; this expert is TWO matrices of `latent x width` (1,024 x 2,688 of a
hidden width of 4,096), and on that count the cell would read about 600%.

An expert layer reads each held expert that any row chose once: its two
matrices. A row (one token's choice of one held expert) is read in and
written out once at `latent` elements, and costs `2 * 2 * latent * width`
operations. Padding rows of a group's last tile, tiles of experts nobody
chose and the dead tiles of the static row bound are the kernel's own cost
and no part of the least. At decode the layer is bound by the weights' bytes
(an 11 MB expert takes 13.4 us to read at 819 GB/s; 5.5 rows through it are
under 1 us of MXU time), so `least_seconds` takes the greater of the two.
"""

from __future__ import annotations


def bytes_moved(experts_read: float, rows: float, *, latent: int, width: int, bytes_per_element: int = 2) -> float:
    return (experts_read * 2 * latent * width + rows * 2 * latent) * bytes_per_element


def flops(rows: float, *, latent: int, width: int) -> float:
    return rows * 2 * 2 * latent * width


def least_seconds(experts_read: float, rows: float, *, latent: int, width: int, peaks: dict,
                  bytes_per_element: int = 2) -> float:
    return max(
        bytes_moved(experts_read, rows, latent=latent, width=width, bytes_per_element=bytes_per_element)
        / peaks["hbm_bytes_per_s"],
        flops(rows, latent=latent, width=width) / peaks["bf16_flops"],
    )
