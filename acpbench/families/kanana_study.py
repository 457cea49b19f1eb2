"""The readings the kanana configuration's `check` limits were set from, made
again by one command on the chip (not run by the benchmark):

    python -m acpbench.families.kanana_study --seeds 3 --engine

This file is the family's two tables and nothing else. The command itself
(`one_seed`, `main`: a line a reading and seed, then each number's smallest
and largest over the seeds; `--engine` adds the engine's own path beside
check.py's structural control `page_swap`; a machine whose device is not
one TPU chip is refused) is `mellum_study`'s, which reads its tables as
module globals: `main` here binds these tables there while it runs, so the
lines are tagged `[mellum_study]`. `lfm2_study`, `jamba_study` and
`mellum_study` are three copies of that command; a fourth was not added,
and giving `acpbench/study.py` the one command that takes a family's tables
is a `benchmark` PR's edit (PERF.md section 7, Open after PR 44).

`CACHE`: keywords of the family's `cached_logits` (`program`: the cache
check as every run makes it; `kv_int8`: the pool holds what int8 latent
rows would; `free_routing`, for the record). `REFERENCE`: `ref_<control>`
for each `lower=` of `kanana_reference` (`ref_int8_matmul_inputs`, the
precision below the stated one; `ref_bf16`, the stated one, which must
pass; `ref_bf16_rest`; `ref_scale_128`, `ref_rope_all`, `ref_kv_norm_off`,
`ref_k_pe_unroped`, `ref_shared_off`, `ref_route_scale_off`, `ref_bias_off`).
"""

from __future__ import annotations

import os
import sys

from . import kanana_reference, mellum_study

CONFIG = "kanana2-30b-a3b-bf16-v5e1-ep16"
CACHE = {"program": {}, "kv_int8": {"kv_int8": True}, "free_routing": {"free_routing": True}}
REFERENCE = tuple("ref_" + name for name in kanana_reference.CONTROLS)


def main(argv=None) -> int:
    theirs = mellum_study.CACHE, mellum_study.REFERENCE
    mellum_study.CACHE, mellum_study.REFERENCE = CACHE, REFERENCE
    try:
        return mellum_study.main(["--config", CONFIG, *(sys.argv[1:] if argv is None else argv)])
    finally:  # the tables go back: the module is `mellum`'s own command too
        mellum_study.CACHE, mellum_study.REFERENCE = theirs


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
