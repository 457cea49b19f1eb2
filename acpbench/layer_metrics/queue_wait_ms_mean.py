"""Scheduler: mean milliseconds from a request's enqueue to its first
admission, over the requests admitted inside the window: the engine's own
counter (`Engine.stats()["scheduler"]["queue_wait"]`), counted where
requests are admitted. This cell files no metric of the program's spans,
so its `[spans]` line (host_spans.py) is printed from here."""

from .. import host_spans
from ._common import delta


def read(run):
    host_spans.analyse(run)
    s, n = delta(run, "scheduler", "queue_wait", "s"), delta(run, "scheduler", "queue_wait", "n")
    if not n or s is None:
        return None
    return s * 1e3 / n
