"""KV manager: the rows the decode steps attended over, over the rows a dense
walk would have read, in %: `stats()["sparse"]["decode"]` `rows_chosen` /
`rows_dense`, deltas over the window. What the indexer saved under this
traffic; a program without the counters gives None."""

from ._common import delta


def read(run):
    chosen, dense = (delta(run, "sparse", "decode", name) for name in ("rows_chosen", "rows_dense"))
    if chosen is None or not dense:
        return None
    return 100.0 * chosen / dense
