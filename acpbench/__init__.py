"""acpbench: the yardstick for `provider: tpu`.

One command runs one cell (a configuration under a traffic mix) once:

    python -m acpbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one metric
is a file of its own, found by the name `BENCHMARK.json` gives it:

    configs/<configuration>.json      sizes, engine options, the deployment, the family's name
    families/<family>.py              the program's config, seeded weights, plain reference, cache check
    traffic/<mix>.json                generator kind and its parameters
    generators/<kind>.py              one general generator per kind
    end_to_end/<metric>.py            read(run) -> value | None
    layer_metrics/<metric>.py         read(run) -> value | None
    kernels/<kernel>.py               operations and bytes from shapes
    peaks.json                        published peaks by device_kind

    systems/<kind>.py                 what a mix is offered to (engine alone, or behind the Operator)

The harness (run.py, loadgen.py, trace_reduce.py, check.py, spec.py) knows
no cell, configuration, metric or model family by name; only a family
module imports the program's models (families/__init__.py).
"""
