"""KV manager: the rows the window layers' decode walks covered over the rows
they would have covered with no window, in %: `stats()["window"]["decode"]`
`rows_read` / `rows_unwindowed`, deltas over the window. What the window
saved under this traffic; a program without the counters gives None."""

from ._common import delta


def read(run):
    read_, whole = (delta(run, "window", "decode", name) for name in ("rows_read", "rows_unwindowed"))
    if read_ is None or not whole:
        return None
    return 100.0 * read_ / whole
