"""Programs: device time of the kernel `masked_prefill_attention` (a whole
prompt's attention under the mask of chosen rows), over all layers, a 1,000
prompt tokens whose first token came inside the traced window, in ms. A
program without the kernel gives None."""

from . import _sparse


def read(run):
    return _sparse.prefill_ms_per_ktok(run, 1)
