"""Scheduler: tokens a live lane commits a verify-and-draft step (1 to 2: a
kept draft that a budget or the context's edge cuts short commits one):
`stats()["drafter"]` deltas over the window. A program without a drafter
gives None."""

from ._common import delta


def read(run):
    tokens, put = delta(run, "drafter", "tokens"), delta(run, "drafter", "proposed")
    if tokens is None or not put:
        return None
    return tokens / put
