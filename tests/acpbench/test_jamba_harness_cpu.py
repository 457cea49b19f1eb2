"""The harness end to end on the CPU for the jamba family at a tiny size
(four layers, one of them attention, `d_inner` 64): weights, engine, warm-up,
ramp, window, drain, readers, output check, as `test_harness_cpu.py` makes
them for the llama family. Nothing here is a device metric."""

import os

import jax
import pytest

from acpbench import run as runner
from acpbench import spec
from acpbench.systems.engine import CompileCounter, System

DATA = os.path.join(os.path.dirname(__file__), "data")
BENCH = spec.benchmark()
CELL = "jamba2-decode-saturated"


@pytest.fixture(scope="module")
def rehearsal():
    config = spec.load_json(os.path.join(DATA, "tiny-config-jamba.json"))
    cell = {"workload": {"name": CELL, "chips": 1}, "config": config,
            "mix": spec.load_json(os.path.join(DATA, "tiny-closed.json"))}
    counter = CompileCounter()
    system = System(config, 2**31 + 11)
    try:
        runner.warm_up(system, cell, 5, counter)
        before = counter.count
        run = runner.measure(system, cell, 5, 2.0, False, "")
        run.setup_s, run.device_kind = 1.0, jax.devices()[0].device_kind
        compiled = counter.count - before
        check = runner.output_check(system, cell, 5)
    finally:
        system.stop()
    return run, compiled, check


def test_nothing_compiles_after_the_warm_up(rehearsal):
    assert rehearsal[1] == 0


def test_requests_are_counted_none_fails_and_none_is_cut_short(rehearsal):
    run = rehearsal[0]
    attempted, failed = runner.count_requests(run)
    assert attempted >= 3 and failed == 0
    ended = [r for r in run.records if r.end_t is not None and not r.censored]
    assert ended and all(r.finish == "length" and r.n_tokens == r.max_tokens for r in ended)


def test_the_cells_end_to_end_metrics_are_read(rehearsal):
    got = runner.read_metrics(BENCH, "end_to_end", rehearsal[0])
    assert set(got) == {"tokens_per_s_per_chip", "setup_s"} and all(v["value"] > 0 for v in got.values())


def test_counters_are_read_and_device_metrics_are_not(rehearsal):
    run = rehearsal[0]
    got = runner.read_metrics(BENCH, "per_layer", run)
    for m in spec.metrics_for(BENCH, CELL, "per_layer"):
        assert (m["name"] in got) == (m["source"] != "device_trace"), m["name"]
    assert 0 < got["batch_occupancy"]["value"] <= 100


def test_the_recurrences_counters_count_over_the_window(rehearsal):
    stats = rehearsal[0].stats
    a, b = stats["open"]["ssm"], stats["close"]["ssm"]
    assert a["state_bytes_per_slot"] == 3 * (16 * 64 * 4 + 3 * 64 * 2)
    steps = stats["close"]["decode_steps"] - stats["open"]["decode_steps"]
    # a snapshot taken while a block is in flight reads the device's counters a block (4 steps) ahead of the host's count
    ran = b["decode"]["mamba_layers"] - a["decode"]["mamba_layers"]
    assert steps > 0 and ran % 3 == 0 and abs(ran - 3 * steps) <= 3 * 4, (ran, steps)
    assert b["prefill"]["tokens"] > a["prefill"]["tokens"] and b["prefill"]["rows"] > a["prefill"]["rows"]
    assert {"state_saves", "state_restores", "state_refused"} <= set(stats["close"]["kv_pages"])


def test_outputs_agree_with_the_reference(rehearsal):
    ok, lines = rehearsal[2]
    assert ok, lines
    for name in ("logit_rel_rms=", "cache_excess=", "greedy_regret="):
        assert any(line.startswith(name) and "limit=" in line for line in lines)
    assert any(line.startswith("stream_mismatch=0 limit=0 ok") for line in lines)
