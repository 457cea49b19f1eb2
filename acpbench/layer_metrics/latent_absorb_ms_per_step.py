"""Programs: device time a decode step of the absorbed projections on either
side of the latent walk (`W_UK` folded into the query before it, `W_UV`
applied after the softmax): the ops of the decode-block runs whose name-stack
path holds the scope `mla_absorb`, in ms. Read from the trace's own op
metadata (`device_scopes.op_table`, joined to the op intervals by program
and event name as `device_scopes.attribute` joins them). A program without
the scope gives None."""

import bisect

from .. import device_scopes, host_spans
from ._common import decode_steps_traced

SCOPE = "mla_absorb"


def seconds(op_intervals, runs, tables, scope: str = SCOPE) -> float:
    """Seconds (mean over chips) of the ops inside decode-block runs whose
    path has `scope` as a component."""
    total = 0
    for ops, (plane, chip_runs) in zip(op_intervals, runs):
        table, starts = tables.get(plane, {}), [r[0] for r in chip_runs]
        spent: dict = {}
        for start, end, event in ops:
            at = bisect.bisect_right(starts, start) - 1
            if at >= 0 and start < chip_runs[at][1] and device_scopes.phase_of(chip_runs[at][2]) == "decode":
                key = (chip_runs[at][3], event)
                spent[key] = spent.get(key, 0) + end - start
        for key, ns in spent.items():
            op = table.get(key)
            if op and any(scope in path.split("/") for path in op.tf_op.split(";")):
                total += ns
    return total / 1e9 / max(1, len(op_intervals))


def read(run):
    if run.trace is None:
        return None
    steps = decode_steps_traced(run)
    path = host_spans.find(run) if steps else None
    if not path:
        return None
    import jax

    runs = device_scopes.module_runs(jax.profiler.ProfileData.from_file(path))
    s = seconds(run.trace["op_intervals"], runs, device_scopes.op_table(path))
    return s * 1e3 / steps if s else None
