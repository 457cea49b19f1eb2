"""Median, over the requests that ended inside the window, of each
request's mean gap between output tokens (metrics.request_gap_ms)."""

from .. import metrics


def read(run):
    gaps = [metrics.request_gap_ms(r) for r in run.records
            if not r.censored and metrics.in_window(r.end_t, run.window)]
    return metrics.percentile([g for g in gaps if g is not None], 50)
