"""Programs: device time in which the prefill runs make the full layers' masks
of chosen rows (the leaves `index_scores`, `index_select` and the rest of
`sparse_mask`) and attend under them (the kernel `masked_prefill_attention`
over expanded latent rows), over the full layers, a 1,000 prompt tokens of
the prompts whose prefill ran in the slice, in ms. A program without the
leaves or the kernel gives None."""

from . import _dots


def read(run):
    return _dots.mask_prefill_ms_per_ktok(run)
