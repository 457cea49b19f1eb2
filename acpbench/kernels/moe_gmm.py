"""The grouped expert matmul (`moe_gmm`): the bytes it must read and the
operations it must do, from counts.

An expert layer reads each held expert that any row chose once: its three
matrices (`hidden x width` gate, up and down). A row (one token's choice of
one held expert) is read in and written out once at `hidden` elements, and
costs `2 * 3 * hidden * width` operations. Padding rows of a group's last
tile and tiles of experts nobody chose are the kernel's own cost and no
part of the least. At decode the layer is bound by the weights' bytes (an
18.9 MB expert takes 23 us to read at 819 GB/s; 32 rows through it are 0.2%
of that in MXU time), so `least_seconds` takes the greater of the two.
"""

from __future__ import annotations


def bytes_moved(experts_read: float, rows: float, *, hidden: int, width: int,
                bytes_per_element: int = 2) -> float:
    return (experts_read * 3 * hidden * width + rows * 2 * hidden) * bytes_per_element


def flops(rows: float, *, hidden: int, width: int) -> float:
    return rows * 2 * 3 * hidden * width


def least_seconds(experts_read: float, rows: float, *, hidden: int, width: int, peaks: dict,
                  bytes_per_element: int = 2) -> float:
    """The least time the chip could take: bytes over its HBM bandwidth or
    operations over its bf16 peak, whichever is greater."""
    return max(
        bytes_moved(experts_read, rows, hidden=hidden, width=width,
                    bytes_per_element=bytes_per_element) / peaks["hbm_bytes_per_s"],
        flops(rows, hidden=hidden, width=width) / peaks["bf16_flops"],
    )
