"""Programs: device time in which the prefill runs make a prompt's mask of
chosen rows (the leaves `index_scores` and `index_select` under
`sparse_mask`, and the rest of that scope: the causal rule, the padding, the
int8 copy the kernel reads), over all layers, a 1,000 prompt tokens whose
first token came inside the traced window, in ms. A program without the
leaves gives None."""

from . import _sparse


def read(run):
    return _sparse.prefill_ms_per_ktok(run, 0)
