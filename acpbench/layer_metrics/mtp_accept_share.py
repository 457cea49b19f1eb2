"""Scheduler: drafts of the model's own drafter that the verify step kept,
over those it put to live lanes, in %: `stats()["drafter"]` deltas over the
window (the device's counters). A program without a drafter gives None."""

from ._common import delta


def read(run):
    kept, put = delta(run, "drafter", "accepted"), delta(run, "drafter", "proposed")
    if kept is None or not put:
        return None
    return 100.0 * kept / put
