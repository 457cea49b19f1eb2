"""Runs one cell once.

    python -m acpbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights on the device from the seed, the engine, the program's own
prewarm, then the cell's generator replayed on another seed until a pass
compiles nothing) is timed from the first line of `main` and reported as
`setup_s`. Then the plan's ramp, the measured window, the drain, and the
output check. The last line of stdout is the result; a machine without
the cell's chips gets no result and exit code 3.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import sys
import time

if __package__ in (None, ""):  # `python acpbench/run.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    __package__ = "acpbench"

from . import loadgen, metrics, spec, trace_reduce  # noqa: E402

WARMUP_MAX_PASSES = 4
WARMUP_ANSWER_TOKENS = 16


def say(tag: str, msg: str) -> None:
    print(f"[{tag}] {msg}", flush=True)


class Run:
    """What one run leaves behind for the metric readers."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def devices_or_exit(chips: int):
    import jax

    devices = jax.devices()
    found = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    if found["platform"] != "tpu" or found["count"] != chips:
        print(f"acpbench: this cell needs {chips} TPU chip(s); jax found {found}", file=sys.stderr, flush=True)
        sys.stdout.flush()
        os._exit(3)
    return devices, found


def warm_up(system, cell: dict, seed: int, counter) -> None:
    """The program's prewarm, then the cell's own traffic on another seed,
    answers cut short, until a pass compiles nothing."""
    mix, config = cell["mix"], cell["config"]
    t0 = time.monotonic()
    system.prewarm()
    say("warmup", f"program prewarm {time.monotonic() - t0:.1f}s, {counter.count} programs compiled or loaded so far")
    gen = spec.generator(mix["kind"])
    short = dict(mix, ramp_s=0)
    for n in range(WARMUP_MAX_PASSES):
        before = counter.count
        plan = gen.plan(short, seed + 7919 * (n + 1), mix["warmup_seconds"], config)
        clip_answers(plan)
        now = time.monotonic()
        system.drive(plan, now, now + mix["warmup_seconds"], 30.0)
        new = counter.count - before
        say("warmup", f"replay pass {n + 1}: {new} new programs, {time.monotonic() - now:.1f}s")
        if new == 0:
            return
    say("warmup", f"still compiling after {WARMUP_MAX_PASSES} passes")


def clip_answers(plan: dict) -> None:
    seqs = [plan["requests"]] if "requests" in plan else plan["clients"]
    for seq in seqs:
        for req in seq:
            req["max_tokens"] = min(req["max_tokens"], WARMUP_ANSWER_TOKENS)


def measure(system, cell: dict, seed: int, seconds: float, trace: bool, trace_dir: str) -> Run:
    """Ramp, window, drain; counters snapshotted at the window's edges and
    the profiler run over a slice of the window."""
    import jax

    mix, config = cell["mix"], cell["config"]
    plan = spec.generator(mix["kind"]).plan(mix, seed, seconds, config)
    t0 = time.monotonic() + 0.2
    window = (t0 + plan["ramp_s"], t0 + plan["ramp_s"] + seconds)
    snaps: dict = {}
    events = [(window[0], lambda: snaps.__setitem__("open", system.stats())),
              (window[1], lambda: snaps.__setitem__("close", system.stats()))]
    traced = None
    if trace:
        span = min(float(mix.get("trace_seconds", 5)), seconds * 0.5)
        traced = [window[0] + (seconds - span) * 0.5, None]

        def start():
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # the host's Python frames cost the host and are not read
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            traced[0] = time.monotonic()
            snaps["trace_start"] = system.stats()

        def stop():
            snaps["trace_stop"] = system.stats()
            traced[1] = time.monotonic()
            jax.profiler.stop_trace()
            snaps["stop_trace_s"] = time.monotonic() - traced[1]

        events += [(traced[0], start), (traced[0] + span, stop)]
    marks = loadgen.Marks(events)
    marks.start()
    records = system.drive(plan, t0, window[1], float(mix["drain_limit_s"]))
    marks.join()
    if marks.errors:
        raise marks.errors[0]
    return Run(cell=cell, mix=mix, config=config, seconds=seconds, chips=cell["workload"]["chips"],
               records=records, window=window, stats=snaps,
               traced=tuple(traced) if traced else None, trace=None, mode=plan["mode"])


def count_requests(run: Run) -> tuple[int, int]:
    """attempted, failed: requests due (open) or ended (closed) inside the
    window; one fails when it errors, is refused, or has not ended when the
    drain limit passes. Requests the benchmark itself cancelled at the
    window's close are censored, not attempted."""
    attempted = failed = 0
    for r in run.records:
        if r.censored:
            continue
        at = (r.end_t if r.end_t is not None else r.due) if run.mode == "closed" else r.due
        if not metrics.in_window(at, run.window):
            continue
        attempted += 1
        failed += r.error is not None
    return attempted, failed


def read_metrics(bench: dict, table: str, run: Run) -> dict:
    out = {}
    for m in spec.metrics_for(bench, run.cell["workload"]["name"], table):
        if m["name"] == "setup_s":
            out["setup_s"] = {"value": run.setup_s, "unit": "s"}
            continue
        value = spec.reader(table, m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def output_numbers(system, config: dict, seed: int) -> dict:
    """Every number the check reads, the compared ones among them: the
    family's cache check and the engine's own path against its reference."""
    from . import check

    family = system.family
    s = check.sample(config["check"], config["vocab_size"], config["engine"]["page_size"], seed)
    got = family.cached_logits(config, system.program_config, system.params, system.mesh, s,
                               system.engine._use_pallas)
    reference = functools.partial(family.reference_logits, config, system.params)
    numbers = check.compare(got, check.reference_logits(reference, s))
    path = check.engine_path(system, s, config["check"]["engine_tokens"])
    numbers.update(check.engine_numbers(reference, s, path))
    return numbers


def output_check(system, cell: dict, seed: int) -> tuple[bool, list[str]]:
    from . import check

    numbers = output_numbers(system, cell["config"], seed)
    ok, lines = check.decide(numbers, cell["config"]["check"]["limits"])
    return ok, lines + [f"engine_tokens={numbers['engine_tokens']} engine_top1_agree={numbers['engine_top1_agree']:.4f}"]


def main(argv=None) -> int:
    t_start = time.monotonic()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(spec.ROOT, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    devices, found = devices_or_exit(cell["workload"]["chips"])

    from .systems.engine import CompileCounter, System

    counter = CompileCounter()
    system = System(cell["config"], args.seed)
    say("setup", f"weights on device in {system.weights_s:.1f}s, engine up at {time.monotonic() - t_start:.1f}s")
    warm_up(system, cell, args.seed, counter)
    setup_s = time.monotonic() - t_start
    say("setup", f"ready in {setup_s:.1f}s")

    trace_dir = os.path.join(spec.ROOT, ".acpbench_trace", f"{args.workload}-{args.seed}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    compiled_before = counter.count
    run = measure(system, cell, args.seed, args.seconds, bool(args.trace), trace_dir)
    run.setup_s, run.device_kind = setup_s, found["kind"]
    in_window = counter.count - compiled_before
    say("window", f"compilations inside the ramp, window and drain: {in_window} (must be 0)")

    attempted, failed = count_requests(run)
    gaps, ttfts = metrics.window_samples(run)
    early = sum(1 for r in run.records if r.finish == "stop")
    say("dist", f"per-request gap ms {json.dumps(metrics.distribution(gaps))}")
    say("dist", f"ttft ms {json.dumps(metrics.distribution(ttfts))}")
    say("dist", f"generator lateness ms {json.dumps(metrics.distribution(loadgen.lateness_ms(run.records)))}")
    stamped = sum(n for r in run.records for t, n in r.blocks if metrics.in_window(t, run.window))
    say("dist", f"output tokens: {metrics.tokens_in_window(run.records, run.window):.1f} produced in the window, "
                f"{stamped} handed over in it (whole blocks, by their stamps)")
    cycles = metrics.cycle_intervals_ms(run.records, run.window)
    say("dist", f"hand-over to hand-over ms {json.dumps(metrics.distribution(cycles))}, {sum(cycles) / 1e3:.3f}s in all")
    steps = {k: run.stats["close"].get(k, 0) - run.stats["open"].get(k, 0) for k in ("decode_steps", "stalls")}
    say("dist", f"engine counters over the window: {json.dumps(steps)}")
    say("dist", f"requests {len(run.records)} sent, {attempted} attempted in the window, {failed} failed, "
                f"{early} ended early on a stop token")

    device = dict(found)
    if args.trace:
        say("trace", f"slice of {run.traced[1] - run.traced[0]:.2f}s; jax.profiler.stop_trace took "
                     f"{run.stats['stop_trace_s']:.1f}s (about 0.1 ms an op event: PERF.md, the run's own clock)")
        path = trace_reduce.find_xplane(trace_dir)
        run.trace = trace_reduce.reduce(path) if path else None
        if run.trace is None:
            say("trace", "no device operation in the trace")
            return 4
        device["busy_s"], device["window_s"] = run.trace["busy_s"], run.trace["window_s"]
    table = "per_layer" if args.trace else "end_to_end"
    out_metrics = read_metrics(bench, table, run)

    # read before the check: its logits and the reference's layers would else be in the program's peak
    stats = [d.memory_stats() or {} for d in devices]
    device["memory_peak_bytes"] = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    ok, lines = output_check(system, cell, args.seed)
    for line in lines:
        say("check", line)
    system.stop()

    result = {"correct": bool(ok and in_window == 0), "attempted": attempted, "failed": failed,
              "metrics": out_metrics, "device": device}
    if run.trace is not None:
        result["breakdown"] = trace_reduce.breakdown(run.trace)
    shutil.rmtree(trace_dir, ignore_errors=True)
    say("run", f"start to result {time.monotonic() - t_start:.1f}s")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # a wedged engine thread must not outlive the verdict
