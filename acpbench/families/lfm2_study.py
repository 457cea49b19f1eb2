"""The readings the lfm2 configuration's `check` limits were set from, made
again by one command on the chip (not run by the benchmark):

    python -m acpbench.families.lfm2_study --config lfm2-24b-a2b-bf16-v5e1-ep8 --seeds 3

For each seed, one line a reading: the sound program (`program`: the
family's cache check as every run makes it), the same with the routing left
free (`free_routing`, for the record: what made the paired reading noise),
the cache's controls (`kv_int8`, `zero_state`), the reference's (`ref_int8`,
`ref_nobias`, `ref_nonorm`, `ref_capacity`), and with `--engine` the
engine's own path beside two structural controls: `page_swap`, check.py's
(one page of 16 tokens holds another request's), and `table_swap`, this
family's (the whole prompt is another request's, as a slot reading another
slot's block table would have it). `acpbench.study outputs` reads the first
five through the same functions; it has no name for `zero_state`,
`free_routing` and `table_swap`, hence this file. The last lines give each
number's smallest and largest over the seeds, a reading a line.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

import numpy as np

from .. import check, spec, study

CACHE = {"program": {}, "free_routing": {"free_routing": True}, "kv_int8": {"quantize_kv": True},
         "zero_state": {"zero_state": True}}
REFERENCE = ("ref_int8", "ref_nobias", "ref_nonorm", "ref_capacity")


def table_swap(reference, s: dict, path: dict) -> float:
    """`greedy_regret` of an emitter that reads ANOTHER request's context:
    the reference's first choice at every position after the next sequence's
    prompt (rolled by one, cut or padded to this one's length) followed by
    this request's own emitted tokens, judged as the engine's tokens are."""
    import jax.numpy as jnp

    emitted = path["returned"]
    R = max(len(e) for e in emitted)
    B, lengths = s["B"], s["lengths"]
    width = max(s["tokens"].shape[1], int(lengths.max()) + R)
    own, other = (np.zeros((B, width), dtype=np.int32) for _ in range(2))
    for b, e in enumerate(emitted):
        n = int(lengths[b])
        own[b, :n] = s["tokens"][b, :n]
        other[b, :n] = s["tokens"][(b + 1) % B, :n]
        own[b, n: n + len(e)] = other[b, n: n + len(e)] = e
    rows = lengths[:, None] - 1 + np.arange(R)[None, :]
    want = reference(own, rows)
    picked = jnp.argmax(reference(other, rows), -1)
    chosen = jnp.take_along_axis(want, picked[..., None], axis=-1)[..., 0]
    return float(jnp.max((jnp.max(want, -1) - chosen) / jnp.std(want, -1)))


def one_seed(config: dict, seed: int, names, engine: bool, use_pallas: bool) -> dict:
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
            got = family.cached_logits(config, program_config, params, mesh, s, use_pallas, **CACHE[name])
        else:
            got = check.reference_logits(reference, s, lower=name[4:])
        out[name] = check.compare(got, want)
    if system is not None:
        path = check.engine_path(system, s, config["check"]["engine_tokens"])
        out["engine"] = check.engine_numbers(reference, s, path)
        out["page_swap"] = {"greedy_regret": check.engine_numbers(reference, s, path, control=True)["greedy_regret"]}
        out["table_swap"] = {"greedy_regret": table_swap(reference, s, path)}
        system.stop()
    return out


def released() -> None:
    """One seed's weights (7.5 GB) off the device before the next seed's
    are drawn: an engine's threads and closures hold them in cycles."""
    import gc

    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


def main(argv=None) -> int:
    import jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="lfm2-24b-a2b-bf16-v5e1-ep8")
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
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
    use_pallas = jax.default_backend() == "tpu"
    seen: dict = {}
    for i in range(args.seeds):
        seed = args.first_seed + 104729 * i
        for name, numbers in one_seed(config, seed, names, args.engine, use_pallas).items():
            print(f"[lfm2_study] seed={seed} {name} {json.dumps(numbers)}", flush=True)
            for key in ("logit_rel_rms", "prefill_rel_rms", "cache_excess", "greedy_regret"):
                if key in numbers:
                    seen.setdefault((name, key), []).append(numbers[key])
        released()
    for (name, key), vals in seen.items():
        print(f"[lfm2_study] {name} {key}: min {min(vals):.6g} max {max(vals):.6g} over {len(vals)} seeds")
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
