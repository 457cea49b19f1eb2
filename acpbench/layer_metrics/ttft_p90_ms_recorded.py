"""Scheduler: time to first token from the due time, 90th percentile over
the requests due inside the window. Recorded, not judged: at four fifths
of the knee one arrival that lands a millisecond either side of a decode
block's end changes who queues behind whom for the rest of the run, and
the tail of ~200 requests then differs by 10-15% between runs of one
trace (PERF.md section 6). A request that failed or got no token
lies beyond any percentile; if the percentile lands there it reads the
drain limit, the longest a request is waited for."""

from .. import metrics


def read(run):
    return metrics.first_event_percentile(run, 90)
