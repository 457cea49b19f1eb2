"""Scheduler: host-to-device uploads of the engine thread per decode block
inside the window: `Engine.stats()["perf"]["uploads"]` (every `Engine._put`
counts one) over the decode blocks dispatched. A dispatch hands its lanes to
the chip as one packed buffer, so a cycle that admits and decodes makes a
handful; a program that has no such counter reads nothing."""

from ._common import delta


def read(run):
    uploads, blocks = delta(run, "perf", "uploads"), delta(run, "perf", "blocks")
    if uploads is None or not blocks:
        return None
    return uploads / blocks
