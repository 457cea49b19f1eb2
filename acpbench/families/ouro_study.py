"""The readings the ouro configuration's `check` limits were set from, made
again by one command on the chip (not run by the benchmark):

    python -m acpbench.families.ouro_study --seeds 3 --engine

This file is the family's two tables and nothing else, as `kanana_study.py`
is and for its reason: the command itself (`one_seed`, `main`) is
`mellum_study`'s, which reads its tables as module globals; `main` here binds
these tables there while it runs, so the lines are tagged `[mellum_study]`.

`CACHE`: keywords of the family's `cached_logits` (`program`: the cache
check as every run makes it; `kv_int8`: the pool holds what int8 pages
would). `REFERENCE`: `ref_<control>` for each `lower=` of `ouro_reference`
(`ref_int8_inputs`, the precision below the stated one; `ref_bf16_rest`, the
stated one, which must pass; `ref_loops_3`, `ref_shared_cache`,
`ref_no_loop_norm`, `ref_no_post_norms`, `ref_exit_first`).
"""

from __future__ import annotations

import os
import sys

from . import mellum_study, ouro_reference

CONFIG = "ouro-2.6b-bf16-v5e1"
CACHE = {"program": {}, "kv_int8": {"kv_int8": True}}
REFERENCE = tuple("ref_" + name for name in ouro_reference.CONTROLS)


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
