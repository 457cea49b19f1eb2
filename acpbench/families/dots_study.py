"""The readings the dots configuration's `check` limits were set from, made
again by one command on the chip (not run by the benchmark):

    python -m acpbench.families.dots_study --seeds 3 --engine

For each seed, one line a reading, `[dots_study] seed=<n> <reading> {numbers}`,
each with `refused_by`, the file's limits it lies past (none: `correct`):

- `planted_<fault>` for each of `dots.CHOICE_FAULTS` (the most recent rows and
  no indexer, half as many rows, the indexer's rope off, its norm off): the
  family's cache check of the PROGRAM with that fault planted in its indexer
  (`dots._planted`), judged as a run of such a program would be;
- `ik_int8`, `kv_int8`, `wkv_int8`: the cache check with that leaf of the pool
  holding what int8 rows would; `ring_minus_1`, `ring_minus_page`: the decode
  steps' sliding layers seeing 1 or 16 rows fewer than the window;
  `ik_crossed`: every sequence's `ik` pages holding its neighbour's rows;
- `program`: the cache check as every run makes it;
- `ref_<control>` for the reference under that control in the program's
  place against the reference: `ref_int8` (the precision below the stated
  one), `ref_bf16`, `ref_bf16_rest`, `ref_gate_off`, `ref_rescale_off`,
  `ref_window_off`, `ref_shared_off` GIVEN the program's choices, and
  `ref_bf16_free`, `ref_index_norm_off`, `ref_index_rope_off` left to choose;
- with `--engine`: `engine`, the engine's own greedy tokens' regret, beside
  the structural controls: check.py's `page_swap` (one page) and
  `pages_crossed` (every second page: `dots.crossed_numbers`).

The last lines give each number's smallest and largest over the seeds. The
command's frame is `keyevl_study`'s, over this family's tables.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from .. import check, spec, study
from . import dots
from .lfm2_study import released

CONFIG = "dots3-note-prev-bf16-v5e1-ep16"
CACHE = {**{"planted_" + fault: {"indexer": fault} for fault in dots.CHOICE_FAULTS},
         "ik_int8": {"ik_int8": True}, "kv_int8": {"kv_int8": True}, "wkv_int8": {"wkv_int8": True},
         "ring_minus_1": {"ring_short": 1}, "ring_minus_page": {"ring_short": 16}, "ik_crossed": {"ik_crossed": True}}
REFERENCE = tuple("ref_" + name for name in dots.GIVEN_UNDER if name) + ("ref_bf16_free", "ref_index_norm_off", "ref_index_rope_off")
NUMBERS = ("logit_rel_rms", "prefill_rel_rms", "cache_excess", "select_miss_prefill", "select_miss_decode",
           "missed_weight", "select_cache_miss", "select_cache_miss_all", "greedy_regret", "regret_median",
           "stream_mismatch")


def one_seed(config: dict, seed: int, names, engine: bool):
    """Yields (reading, numbers) as each is made: a call cut short keeps what it read."""
    import numpy as np

    system = None
    if engine:
        from ..systems.engine import System

        system = System(config, seed)
        program_config, mesh, params = system.program_config, system.mesh, system.params
    else:
        program_config, mesh, params = study._engine_free_system(config, seed)
    s = check.sample(config["check"], config["vocab_size"], config["engine"]["page_size"], seed)
    limits = {**config["check"]["limits"], **config["check"]["select_limits"]}

    def judged(numbers: dict) -> dict:
        past = [name for name, limit in limits.items() if name in numbers and not numbers[name] <= limit]
        return {**numbers, "refused_by": past + ([] if numbers.get("finite", True) else ["finite"])}

    def cache_check(**control):
        pre, dec, chosen = dots.cache_readings(config, program_config, params, mesh, s, True, **control)
        want = dots.reference_logits(config, params, s["tokens"], s["rows"])  # given what this program chose
        return judged({**check.compare((pre, dec), want), **chosen}), want

    for name in names:
        if name in CACHE:
            yield name, cache_check(**CACHE[name])[0]
    # the sound program LAST of the cache checks: what the reference is given from here on is its choice
    program, want = cache_check()
    yield "program", program
    for name in names:
        if name in REFERENCE:
            got = dots.reference_logits(config, params, s["tokens"], s["rows"], lower=name[4:])
            yield name, judged(check.compare(got, want))
    if system is not None:
        asked: dict = {}

        def reference(tokens, rows, lower=None):  # a pass is seconds: the three engine readings share the given one
            key = (np.asarray(tokens).tobytes(), np.asarray(rows).tobytes(), lower)
            if key not in asked:
                asked[key] = dots.reference_logits(config, params, tokens, rows, lower=lower)
            return asked[key]

        path = check.engine_path(system, s, config["check"]["engine_tokens"])
        yield "engine", judged(check.engine_numbers(reference, s, path))
        yield "page_swap", judged({"greedy_regret": check.engine_numbers(reference, s, path, control=True)["greedy_regret"]})
        if s["B"] > 1:
            for name, numbers in dots.crossed_numbers(reference, s, path).items():
                yield name, judged(numbers)
        system.stop()
    # what the family keeps for the harness's next question holds this seed's weights (5 GB): let them go with it
    dots._GIVEN.clear()
    dots._CHOOSER.clear()


def main(argv=None) -> int:
    from ..run import devices_or_exit

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=6_100_000_043)
    ap.add_argument("--readings", default=",".join([*REFERENCE, *CACHE]))
    ap.add_argument("--engine", action="store_true")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(spec.ROOT, ".jax_cache"))
    conf = next(c for c in spec.benchmark()["configs"] if c["name"] == CONFIG)
    config = spec.load_json(os.path.join(spec.ROOT, conf["file"]))
    names = [n for n in args.readings.split(",") if n]
    unknown = [n for n in names if n not in CACHE and n not in REFERENCE]
    if unknown:
        raise SystemExit(f"unknown readings {unknown}; known: {', '.join([*REFERENCE, *CACHE])}")
    devices_or_exit(1)
    seen: dict = {}
    for i in range(args.seeds):
        seed = args.first_seed + 104729 * i
        for name, numbers in one_seed(config, seed, names, args.engine):
            print(f"[dots_study] seed={seed} {name} {json.dumps(numbers)}", flush=True)
            for key in NUMBERS:
                if key in numbers:
                    seen.setdefault((name, key), []).append(numbers[key])
        released()
    for (name, key), vals in seen.items():
        print(f"[dots_study] {name} {key}: min {min(vals):.6g} max {max(vals):.6g} over {len(vals)} seeds")
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
