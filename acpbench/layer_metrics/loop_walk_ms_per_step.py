"""Programs: device time of a looped model's page walks (`paged_page_walk`,
`num_hidden_layers x total_ut_steps` of them) a decode step, in ms, over the
traced slice's decode steps."""

from ._loops import walk_seconds


def read(run):
    found = walk_seconds(run)
    return None if found is None else found[1] * 1e3 / found[0]
