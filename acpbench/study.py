"""One-off studies on the chip that the committed numbers rest on. Not
run by the benchmark; kept so that a reader can make the readings again.

    python -m acpbench.study outputs --config <name> --seeds 12 [--control program|kv_int8|ref_int8|ref_fp8|ref_nobias]
        the output check's readings over seeds, in one process; without a
        control it builds the engine for each seed, reads the engine's path
        too, and beside it the structural control (a swapped page).
        `program` is the sound program's logits with no engine built,
        `kv_int8` the same through the program's int8 KV pages, `ref_<name>`
        the family's reference under its control <name> (llama: int8, fp8,
        and nobias, the q, k and v biases left out)
    python -m acpbench.study sweep --workload <cell> --rates 1,2,3,4,5 --seconds 30
        an open-loop cell at several rates in one process: where the knee is
    python -m acpbench.study small-trace --out chiprun_out/small_trace
        a small recorded trace of two jitted programs, for the reducer's test
    python -m acpbench.study inventory --trace <file.xplane.pb>
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

from . import check, metrics, spec, trace_reduce


def _engine_free_system(config: dict, seed: int):
    """The family's config for the program, a mesh and the seeded weights,
    with no engine."""
    import jax

    from agentcontrolplane_tpu.parallel.mesh import make_mesh

    family = spec.family(config)
    tp = config["engine"].get("tensor_parallelism", 1)
    program_config = family.program_config(config)
    mesh = make_mesh({"tp": tp}, devices=jax.devices()[:tp])
    return program_config, mesh, family.weights(config, program_config, mesh, seed)


def readings(config: dict, seed: int, control: str, use_pallas: bool) -> dict:
    """The output check's numbers at one seed, through the configuration's
    family. `ref_<lower>` puts the family's reference under that control in
    the program's place; `kv_int8` is the program's own int8 KV pages."""
    family = spec.family(config)
    system = None
    if control == "none":
        from .systems.engine import System

        system = System(config, seed)
        program_config, mesh, params = system.program_config, system.mesh, system.params
    else:
        program_config, mesh, params = _engine_free_system(config, seed)
    reference = functools.partial(family.reference_logits, config, params)
    s = check.sample(config["check"], config["vocab_size"], config["engine"]["page_size"], seed)
    want = check.reference_logits(reference, s)
    if control.startswith("ref_"):
        got = check.reference_logits(reference, s, lower=control[4:])
    else:
        lower = {"quantize_kv": True} if control == "kv_int8" else {}
        got = family.cached_logits(config, program_config, params, mesh, s, use_pallas, **lower)
    numbers = check.compare(got, want)
    if system is not None:
        path = check.engine_path(system, s, config["check"]["engine_tokens"])
        numbers.update(check.engine_numbers(reference, s, path))
        swapped = check.engine_numbers(reference, s, path, control=True)
        print(f"[outputs] control=page_swap seed={seed} {json.dumps(swapped)}", flush=True)
        system.stop()
    return numbers


def outputs(args) -> int:
    import jax

    bench = spec.benchmark()
    conf = next(c for c in bench["configs"] if c["name"] == args.config)
    config = spec.load_json(os.path.join(spec.ROOT, conf["file"]))
    use_pallas = jax.default_backend() == "tpu"
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 104729 * i
        numbers = readings(config, seed, args.control, use_pallas)
        rows.append(numbers)
        print(f"[outputs] control={args.control} seed={seed} {json.dumps(numbers)}", flush=True)
    for key in ("logit_rel_rms", "cache_excess", "greedy_regret"):
        vals = [r[key] for r in rows if key in r]
        if not vals:
            continue
        print(f"[outputs] control={args.control} {key}: min {min(vals):.6g} max {max(vals):.6g} over {len(vals)} seeds")
    return 0


def sweep(args) -> int:
    from . import run as runner
    from .systems.engine import CompileCounter, System

    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)
    runner.devices_or_exit(cell["workload"]["chips"])
    counter = CompileCounter()
    system = System(cell["config"], args.seed)
    runner.warm_up(system, cell, args.seed, counter)
    for rate in [float(r) for r in args.rates.split(",")]:
        one = dict(cell, mix=dict(cell["mix"], rate_per_s=rate))
        run = runner.measure(system, one, args.seed, args.seconds, False, "")
        due = [r for r in run.records if metrics.in_window(r.due, run.window)]
        gaps, ttft = metrics.window_samples(run)
        tokens = metrics.tokens_in_window(run.records, run.window)
        late = [r for r in due if r.first_t is None]
        half = len(ttft) // 2
        print(f"[sweep] rate={rate} due={len(due)} no_first_token={len(late)} tokens_per_s={tokens / args.seconds:.1f} "
              f"ttft_p50={metrics.percentile(ttft, 50)} ttft_p90={metrics.percentile(ttft, 90)} "
              f"ttft_first_half_p50={metrics.percentile(ttft[:half], 50)} ttft_second_half_p50={metrics.percentile(ttft[half:], 50)} "
              f"gap_p50={metrics.percentile(gaps, 50)} waiting_at_close={run.stats['close'].get('waiting')} "
              f"active_at_close={run.stats['close'].get('active_slots')}", flush=True)
    system.stop()
    return 0


def small_trace(args) -> int:
    import glob
    import shutil

    import jax
    import jax.numpy as jnp

    @jax.jit
    def decode_block(x):
        return jnp.tanh(x @ x)

    @jax.jit
    def prefill_and_sample(x):
        return jnp.sum(x * 2.0, axis=0)

    x = jnp.ones((256, 256), jnp.bfloat16)
    jax.block_until_ready((decode_block(x), prefill_and_sample(x)))
    tmp = args.out + ".tmp"
    jax.profiler.start_trace(tmp)
    for _ in range(3):
        x = decode_block(x)
        jax.block_until_ready(prefill_and_sample(x))
        time.sleep(0.002)
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(tmp)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    shutil.copy(path, args.out + ".xplane.pb")
    shutil.rmtree(tmp, ignore_errors=True)
    print("\n".join(trace_reduce.inventory(args.out + ".xplane.pb")))
    reduced = trace_reduce.reduce(args.out + ".xplane.pb") or {}
    print(json.dumps({k: v for k, v in reduced.items() if k != "op_intervals"}))
    return 0


def inventory(args) -> int:
    print("\n".join(trace_reduce.inventory(args.trace, events=args.events)))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    p = sub.add_parser("outputs")
    p.add_argument("--config", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--first-seed", type=int, default=2_200_000_001)
    p.add_argument("--control", default="none")
    p = sub.add_parser("sweep")
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=30)
    p.add_argument("--seed", type=int, default=2_300_000_001)
    p = sub.add_parser("small-trace")
    p.add_argument("--out", required=True)
    p = sub.add_parser("inventory")
    p.add_argument("--trace", required=True)
    p.add_argument("--events", type=int, default=6)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(spec.ROOT, ".jax_cache"))
    return {"outputs": outputs, "sweep": sweep, "small-trace": small_trace, "inventory": inventory}[args.cmd](args)


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
