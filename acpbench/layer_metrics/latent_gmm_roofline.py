"""Kernels: the least time the chip could take for the decode steps' grouped
matmuls over latent, ungated experts (kernels/latent_gmm.py: the greater of
bytes over the HBM bandwidth and operations over the bf16 peak, from the
`moe` counters' deltas over the traced slice) over the `moe_gmm` kernel's
device time in decode steps (_moe.py finds them), in %. A file without
`moe_latent_size` (a gated expert at the hidden width: `moe_gmm_roofline`)
gives None."""

from .. import peaks
from ..kernels import latent_gmm
from ._common import decode_steps_traced
from ._moe import decode_expert_seconds, per_decode_step


def read(run):
    c = run.config
    if "moe_latent_size" not in c:
        return None
    read_a_step, rows_a_step = per_decode_step(run, "experts_read"), per_decode_step(run, "pairs_held")
    found, steps = decode_expert_seconds(run), decode_steps_traced(run)
    if read_a_step is None or rows_a_step is None or not found or not steps:
        return None
    least = latent_gmm.least_seconds(read_a_step * steps, rows_a_step * steps, latent=c["moe_latent_size"],
                                     width=c["moe_intermediate_size"], peaks=peaks.peaks(run.device_kind))
    return 100.0 * least / found[0]
