"""Collectives: all-reduce device time during which no other operation
runs on that chip, over the traced window, in %."""

from .. import trace_reduce


def read(run):
    if run.trace is None or run.trace["devices"] < 2:
        return None
    return 100.0 * trace_reduce.exposed_seconds(run.trace, r"all-reduce|all_reduce") / run.trace["window_s"]
