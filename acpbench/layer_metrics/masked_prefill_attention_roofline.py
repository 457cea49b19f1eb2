"""Kernels: the least time the chip could take for the KEPT pairs (a query's
`topk` chosen rows) of the prompts whose first token came inside the traced
window (kernels/masked_attention.py: bound by operations) over the device time of
the kernel `masked_prefill_attention` in the slice, in %. A program without
the kernel gives None."""

from .. import peaks
from ..kernels import masked_attention
from . import _sparse


def read(run):
    got = _sparse.prefill_seconds(run)
    if got is None:
        return None
    c = run.config
    least = masked_attention.least_seconds(
        _sparse.prompt_lengths(run), peaks.peaks(run.device_kind), topk=c["sa_config"]["topk"], heads=c["num_attention_heads"],
        kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"], n_layers=c["num_hidden_layers"])
    return 100.0 * least / got[1]
