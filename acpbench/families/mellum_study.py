"""The readings the mellum configuration's `check` limits were set from, made
again by one command on the chip (not run by the benchmark):

    python -m acpbench.families.mellum_study --config mellum2-12b-a2.5b-bf16-v5e1-ep4 --seeds 3 --engine

For each seed, one line a reading: the sound program (`program`: the
family's cache check as every run makes it), the cache's controls
(`window_minus_page`: the decode steps walk a page less of the ring than the
window; `kv_int8`: both caches hold what int8 pages would; `free_routing`,
for the record), the reference's (`ref_int8`, the precision below the
stated one; `ref_bf16`, the stated one, which must pass; `ref_bf16_rest`,
the stated one in every tensor the program keeps at rest; `ref_window_off`;
`ref_one_rope`; `ref_nonorm`), and with `--engine` the engine's own path
beside check.py's structural control `page_swap` (one page of 16 tokens
holds another request's). The last lines give each number's smallest and
largest over the seeds, a reading a line. Like `acpbench.run`, the command
refuses a machine whose device is not one TPU chip. `lfm2_study.py` is the
same command for its family; the release of a seed's weights is its.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

from .. import check, spec, study
from .lfm2_study import released

CACHE = {"program": {}, "window_minus_page": {"window_minus_page": True}, "kv_int8": {"kv_int8": True},
         "free_routing": {"free_routing": True}}
REFERENCE = ("ref_int8", "ref_bf16", "ref_bf16_rest", "ref_window_off", "ref_one_rope", "ref_nonorm")
NUMBERS = ("logit_rel_rms", "prefill_rel_rms", "cache_excess", "greedy_regret", "stream_mismatch")


def one_seed(config: dict, seed: int, names, engine: bool) -> dict:
    family = spec.family(config)
    system = None
    if engine:
        from ..systems.engine import System

        system = System(config, seed)
        program_config, mesh, params = system.program_config, system.mesh, system.params
    else:
        program_config, mesh, params = study._engine_free_system(config, seed)
    reference = functools.partial(family.reference_logits, config, params)
    s = check.sample(config["check"], config["vocab_size"], config["engine"]["page_size"], seed)
    want = check.reference_logits(reference, s)
    out = {}
    for name in names:
        if name in CACHE:
            got = family.cached_logits(config, program_config, params, mesh, s, True, **CACHE[name])
        else:
            got = check.reference_logits(reference, s, lower=name[4:])
        out[name] = check.compare(got, want)
    if system is not None:
        path = check.engine_path(system, s, config["check"]["engine_tokens"])
        out["engine"] = check.engine_numbers(reference, s, path)
        out["page_swap"] = {"greedy_regret": check.engine_numbers(reference, s, path, control=True)["greedy_regret"]}
        system.stop()
    return out


def main(argv=None) -> int:
    from ..run import devices_or_exit

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="mellum2-12b-a2.5b-bf16-v5e1-ep4")
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=4_000_000_007)
    ap.add_argument("--readings", default=",".join([*CACHE, *REFERENCE]))
    ap.add_argument("--engine", action="store_true")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(spec.ROOT, ".jax_cache"))
    conf = next(c for c in spec.benchmark()["configs"] if c["name"] == args.config)
    config = spec.load_json(os.path.join(spec.ROOT, conf["file"]))
    names = [n for n in args.readings.split(",") if n]
    unknown = [n for n in names if n not in CACHE and n not in REFERENCE]
    if unknown:
        raise SystemExit(f"unknown readings {unknown}; known: {', '.join([*CACHE, *REFERENCE])}")
    devices_or_exit(1)
    seen: dict = {}
    for i in range(args.seeds):
        seed = args.first_seed + 104729 * i
        for name, numbers in one_seed(config, seed, names, args.engine).items():
            print(f"[mellum_study] seed={seed} {name} {json.dumps(numbers)}", flush=True)
            for key in NUMBERS:
                if key in numbers:
                    seen.setdefault((name, key), []).append(numbers[key])
        released()
    for (name, key), vals in seen.items():
        print(f"[mellum_study] {name} {key}: min {min(vals):.6g} max {max(vals):.6g} over {len(vals)} seeds")
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
