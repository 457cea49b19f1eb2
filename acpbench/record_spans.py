"""Records a small profiler trace of the program's engine with its spans,
for the test of `host_spans`. Not run by the benchmark.

    python -m acpbench.record_spans --out chiprun_out/small_spans

A tiny engine (the CPU rehearsal's configuration: random weights, 2 layers)
serves a few short greedy requests under `jax.profiler`, with `run.py`'s
own profiler options; the `.xplane.pb` is copied to `<out>.xplane.pb` and
read back. On a chip the file holds the device planes beside the host
plane's `acp.*` spans, which is what the test needs; on a CPU only the
host plane.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

from . import host_spans, spec, trace_reduce

CONFIG = os.path.join(spec.ROOT, "tests", "acpbench", "data", "tiny-config.json")
REQUESTS, TOKENS, SEED = 3, 8, 2_400_000_001  # a few cycles: the file is kept among the tests' data


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax

    from .systems.engine import System

    system = System(spec.load_json(CONFIG), SEED)

    def serve() -> None:
        futures = [system.submit({"prompt": [(7 * i + j) % 500 for j in range(40 + 8 * i)],
                                  "max_tokens": TOKENS}, None) for i in range(REQUESTS)]
        for f in futures:
            f.result(timeout=600)

    serve()  # every shape compiled before the trace
    serve()
    tmp = args.out + ".tmp"
    shutil.rmtree(tmp, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=options)
    serve()
    jax.profiler.stop_trace()
    phases = system.stats()["perf"]["phases"]
    system.stop()

    path = trace_reduce.find_xplane(tmp)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    shutil.copy(path, args.out + ".xplane.pb")
    shutil.rmtree(tmp, ignore_errors=True)
    profile = jax.profiler.ProfileData.from_file(args.out + ".xplane.pb")
    spans = host_spans.read_profile(profile)
    reduced = trace_reduce.reduce_profile(profile)
    print(json.dumps({"bytes": os.path.getsize(args.out + ".xplane.pb"), "spans": len(spans),
                      "names": sorted({s[2] for s in spans}), "phases": phases}))
    if reduced is not None:
        print("[spans] " + host_spans.line(host_spans.analyse_profile(profile, reduced), reduced))
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
