"""Programs: held experts read a decode step and expert layer, over the
experts held, in %: `stats()["moe"]["decode"]` deltas over the window. At
32 slots, 4 of 64 a token, 1 - (15/16)**32 = 87% whatever the seed."""

from ._common import delta


def read(run):
    read_, layers = delta(run, "moe", "decode", "experts_read"), delta(run, "moe", "decode", "expert_layers")
    held = run.stats.get("open", {}).get("moe", {}).get("held")
    if read_ is None or not layers or not held:
        return None
    return 100.0 * read_ / (layers * held)
