"""KV manager: requests preempted for pages inside the window."""

from ._common import delta


def read(run):
    n = delta(run, "preemptions")
    return None if n is None else float(n)
