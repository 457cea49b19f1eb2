"""The readings the Nemotron 3 Super configuration's `check` limits were set
from, made again by one command on the chip (not run by the benchmark):

    python -m acpbench.families.nemotron_h_study --seeds 3 --engine

The family's two tables over `jamba_study`'s command (as `kanana_study` and
`exaone_study` ride `mellum_study`'s: the seed loop, the state's own numbers
and the engine's path are that file's, so the lines are tagged
`[jamba_study]`). `CACHE`: keywords of the family's `cache_readings`
(`program`: the cache check as every run makes it; `h_bf16`: the stored `S`
rounded to bfloat16, the precision below the stated one; `zero_state`;
`state_swap`; `recurrence_bf16`: bfloat16 inside the decode steps' update;
`free_routing`, for the record). `REFERENCE`: `ref_<control>`
for each `lower=` of `nemotron_h_reference` ("int8", "bf16" which must pass,
"recurrence_bf16", "decay_quotient", "latent_skip"). It refuses a machine
without a TPU, as `acpbench.run` does.
"""

from __future__ import annotations

import os
import sys

from . import jamba_study, nemotron_h_reference

CONFIG = "nemotron3-super-120b-a12b-bf16-v5e1-ep8"
CACHE = {"program": {}, "h_bf16": {"h_bf16": True}, "zero_state": {"zero_state": True},
         "state_swap": {"state_swap": True}, "recurrence_bf16": {"recurrence_bf16": True},
         "free_routing": {"free_routing": True}}
REFERENCE = tuple("ref_" + name for name in nemotron_h_reference.CONTROLS)


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    theirs = jamba_study.CACHE, jamba_study.REFERENCE
    jamba_study.CACHE, jamba_study.REFERENCE = CACHE, REFERENCE
    try:
        if "--readings" not in argv:
            argv = ["--readings", ",".join([*CACHE, *REFERENCE]), *argv]
        return jamba_study.main(["--config", CONFIG, *argv])
    finally:  # the tables go back: the module is `jamba`'s own command too
        jamba_study.CACHE, jamba_study.REFERENCE = theirs


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
