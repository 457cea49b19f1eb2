"""Programs: the least time the chip could take to read the weights a looped
model's decode step must read (kernels/loop_weights.py: `total_ut_steps x`
the layers' matrices and the head once, over the HBM peak) over the device
time a step of the decode blocks' ops under the leaves where those weights
are read (`attn_qkv`, `attn_out`, `ffn_dense`, the head's product), in %:
how near the step's matmuls are to their bytes' floor. The leaves hold their
norms, rotary and residual adds too, which only lowers the share."""

from .. import peaks
from ..kernels import loop_weights
from ._common import decode_steps_traced
from ._loops import MATMUL_LEAVES, leaf_seconds


def read(run):
    found = leaf_seconds(run)
    if found is None:
        return None
    spent = sum(found[name] for name in MATMUL_LEAVES)
    if not spent:
        return None
    least_s = loop_weights.from_config(run.config) / peaks.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s * decode_steps_traced(run) / spent
