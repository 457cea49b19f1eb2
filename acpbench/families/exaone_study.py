"""The readings the K-EXAONE configuration's `check` limits were set from,
made again by one command on the chip (not run by the benchmark):

    python -m acpbench.families.exaone_study --seeds 3 --engine

The family's two tables over `mellum_study`'s command (`kanana_study` says
how and why: the lines are tagged `[mellum_study]`), and after them one
reading of its own a seed, tagged `[exaone_study]`: `draft_rel_rms`, the
drafted logits each teacher-forced verify step starts from, through the
MTP block's pages, against the plain reference's `mtp_logits`, beside the
same number for the reference's control `mtp_prev_hidden_off`, which it has
to stand well under. `correct` cannot hold the drafted logits (it takes no
number from a family: PERF.md section 7), so this is where they are held on
the chip.

`CACHE`: keywords of the family's `cached_logits` (`program`: the cache
check as every run makes it, verify steps half of whose drafts are refused;
`window_minus_page`; `draft_row_kept`: a refused row left counted;
`kv_int8`; `free_routing`, for the record). `REFERENCE`: `ref_<control>` for
each `lower=` of `exaone_reference` but the drafter's own.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

from .. import check, spec, study
from . import exaone_reference, mellum_study
from .lfm2_study import released

CONFIG = "k-exaone-236b-a23b-bf16-v5e1-ep8"
CACHE = {"program": {}, "window_minus_page": {"window_minus_page": True}, "draft_row_kept": {"draft_row_kept": True},
         "kv_int8": {"kv_int8": True}, "free_routing": {"free_routing": True}}
REFERENCE = tuple("ref_" + name for name in exaone_reference.CONTROLS if name != "mtp_prev_hidden_off")


def draft_reading(config: dict, seed: int) -> dict:
    """`draft_rel_rms` of one seed's weights: the program's drafted logits
    and the control's against `mtp_logits`."""
    import jax.numpy as jnp

    family = spec.family(config)
    program_config, mesh, params = study._engine_free_system(config, seed)
    s = check.sample(config["check"], config["vocab_size"], config["engine"]["page_size"], seed)
    _pre, _dec, drafted, started = family.cached_logits(config, program_config, params, mesh, s, True, draft=True)
    rows = s["lengths"][:, None] - 1 + np.arange(s["N"])[None, :]
    want = family.reference_draft_logits(config, params, s["tokens"], rows)
    m = jnp.asarray(started)[..., None]
    rel = lambda got: float(jnp.sqrt(jnp.sum(jnp.where(m, (got - want) ** 2, 0.0)) / jnp.sum(jnp.where(m, want ** 2, 0.0))))  # noqa: E731
    control = family.reference_draft_logits(config, params, s["tokens"], rows, lower="mtp_prev_hidden_off")
    return {"draft_rel_rms": rel(drafted), "rows": int(started.sum()), "ref_mtp_prev_hidden_off": rel(control),
            "ref_bf16": rel(family.reference_draft_logits(config, params, s["tokens"], rows, lower="bf16")),
            "ref_int8": rel(family.reference_draft_logits(config, params, s["tokens"], rows, lower="int8"))}


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    theirs = mellum_study.CACHE, mellum_study.REFERENCE
    mellum_study.CACHE, mellum_study.REFERENCE = CACHE, REFERENCE
    try:
        code = mellum_study.main(["--config", CONFIG, *argv])
    finally:  # the tables go back: the module is `mellum`'s own command too
        mellum_study.CACHE, mellum_study.REFERENCE = theirs
    seeds = int(argv[argv.index("--seeds") + 1]) if "--seeds" in argv else 3
    first = int(argv[argv.index("--first-seed") + 1]) if "--first-seed" in argv else 4_000_000_007
    conf = next(c for c in spec.benchmark()["configs"] if c["name"] == CONFIG)
    config = spec.load_json(os.path.join(spec.ROOT, conf["file"]))
    for i in range(seeds):
        seed = first + 104729 * i
        print(f"[exaone_study] seed={seed} {json.dumps(draft_reading(config, seed))}", flush=True)
        released()
    return code


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
