"""Scheduler: tokens committed over decode steps times slots, in %."""

from ._common import delta


def read(run):
    steps, tokens = delta(run, "decode_steps"), delta(run, "tokens_generated")
    if not steps or tokens is None:
        return None
    return 100.0 * tokens / (steps * run.stats["open"]["max_slots"])
