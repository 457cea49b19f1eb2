"""Programs: device ms a decode step under the drafter's own scopes
(`mtp_in_proj`, `mtp_block`, `mtp_head`), in decode-block runs of the traced
slice, summed leaf by leaf as `latent_absorb_ms_per_step` sums its one (an
op fused across two of them counts under each). The MTP block's page walk is
a Pallas kernel whose event may carry no path, and is then not counted here
(`verify_walk_roofline` times it). A program without those scopes gives
None."""

from .. import device_scopes, host_spans
from ._common import decode_steps_traced
from .latent_absorb_ms_per_step import seconds

SCOPES = ("mtp_in_proj", "mtp_block", "mtp_head")


def read(run):
    steps = decode_steps_traced(run)
    path = host_spans.find(run) if steps else None
    if not path:
        return None
    import jax

    runs = device_scopes.module_runs(jax.profiler.ProfileData.from_file(path))
    tables = device_scopes.op_table(path)
    s = sum(seconds(run.trace["op_intervals"], runs, tables, scope) for scope in SCOPES)
    return s * 1e3 / steps if s else None
