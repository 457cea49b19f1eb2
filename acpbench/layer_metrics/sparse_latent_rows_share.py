"""KV manager: the latent rows the full layers' decode steps attended over,
over the rows a dense latent walk would have read, in %:
`stats()["sparse"]["decode"]` `rows_chosen` / `rows_dense`, deltas over the
window, of a program that also counts its sliding layers' ring
(`stats()["window"]`: the two latent ranks). What the indexer saved under
this traffic; a program without the counters gives None."""

from ._common import delta


def read(run):
    chosen, dense = (delta(run, "sparse", "decode", name) for name in ("rows_chosen", "rows_dense"))
    if chosen is None or not dense or delta(run, "window", "decode", "rows_read") is None:
        return None
    return 100.0 * chosen / dense
