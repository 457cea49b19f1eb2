"""Programs: device ms a decode step of the LatentMoE mixer, from the router's
product to the end of the projection back up, with the shared expert: the
ops of decode-block runs whose path holds one of the mixer's scopes
(`moe_route`, `latent_down`, `moe_sort`, `moe_gmm`, `moe_combine`,
`latent_up`, `moe_shared`), summed scope by scope as `mtp_draft_ms_per_step`
sums its three (an op fused across two of them counts under each), plus the
`moe_gmm` kernels' own events where they carry no path (_moe.py times them).
A program without `latent_down` (another family, a parent commit) gives
None."""

from .. import device_scopes, host_spans
from ._common import decode_steps_traced
from ._moe import decode_expert_seconds
from .latent_absorb_ms_per_step import seconds

SCOPES = ("moe_route", "latent_down", "moe_sort", "moe_gmm", "moe_combine", "latent_up", "moe_shared")


def read(run):
    if run.trace is None:
        return None
    steps = decode_steps_traced(run)
    path = host_spans.find(run) if steps else None
    if not path:
        return None
    import jax

    runs = device_scopes.module_runs(jax.profiler.ProfileData.from_file(path))
    tables = device_scopes.op_table(path)
    by_scope = {scope: seconds(run.trace["op_intervals"], runs, tables, scope) for scope in SCOPES}
    if not by_scope["latent_down"]:
        return None
    kernels = decode_expert_seconds(run)
    if kernels and by_scope["moe_gmm"] < kernels[0]:  # the kernels' events carry no path: their time from their names
        by_scope["moe_gmm"] = kernels[0]
    return sum(by_scope.values()) * 1e3 / steps
