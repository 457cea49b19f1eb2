"""Programs: device time of the prefill and continuation programs over the
thousands of prompt tokens whose first token came inside the traced window."""

from .. import metrics, trace_reduce
from ._common import PREFILL, traced_window


def read(run):
    window = traced_window(run)
    if window is None:
        return None
    tokens = sum(r.prompt_len for r in run.records if metrics.in_window(r.first_t, window))
    seconds = trace_reduce.seconds_of(run.trace, "modules", PREFILL)
    if not tokens or not seconds:
        return None
    return seconds * 1e3 / (tokens / 1e3)
