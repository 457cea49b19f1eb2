"""Programs: passes of the stack a decoded token, `stats()["loops"]["decode"]`
`passes` over `tokens` across the window: `total_ut_steps` (4.0) while every
token runs every loop; a change that drops a loop shows here as well as in
`correct`."""

from ._common import delta


def read(run):
    passes, tokens = delta(run, "loops", "decode", "passes"), delta(run, "loops", "decode", "tokens")
    return passes / tokens if passes is not None and tokens else None
