"""The reduction from a profiler trace to numbers: on a small trace
recorded on a v5e (two jitted programs, three runs each, of which the
first and the last run of the trace are dropped as a slice's cut runs
are), and on made-up planes for the overlap arithmetic."""

import os
from types import SimpleNamespace as NS

import pytest

from acpbench import trace_reduce as tr

SMALL = os.path.join(os.path.dirname(__file__), "data", "small_trace.xplane.pb")


@pytest.fixture(scope="module")
def small():
    return tr.reduce(SMALL)


def test_recorded_trace_has_one_device_and_both_programs(small):
    assert small["devices"] == 1
    assert small["modules"]["jit_decode_block"]["n"] == 2  # the trace's first run is dropped
    assert small["modules"]["jit_prefill_and_sample"]["n"] == 2  # and its last
    assert 0 < small["busy_s"] < small["window_s"] < 0.1
    # the window opens where the first run ended, before the first op kept, and closes with the last run kept
    (start, end), kept = small["windows"][0], small["op_intervals"][0]
    assert start < min(s for s, _, _ in kept) and end == max(e for _, e, _ in kept)
    assert small["window_s"] == pytest.approx((end - start) / 1e9)
    assert 0 < small["slice_s"][0] < 1e-4 and small["slice_s"][1] - small["slice_s"][0] == pytest.approx(small["window_s"])


def test_recorded_trace_reduces_by_pattern(small):
    assert tr.runs_of(small, r"decode_block") == 2
    assert tr.seconds_of(small, "modules", r"prefill|continue") == pytest.approx(
        small["modules"]["jit_prefill_and_sample"]["s"])
    assert tr.seconds_of(small, "ops", r"tanh") > 0
    assert tr.seconds_of(small, "ops", r"page_walk") == 0
    b = tr.breakdown(small)
    assert len(b["device_ops"]) <= 10 and b["idle_gaps"][0][0] == "jit_prefill_and_sample-jit_decode_block"
    assert sum(v for _, v in b["idle_gaps"]) < small["window_s"]


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def plane(name, ops, mods):
    return NS(name=name, lines=[NS(name="XLA Ops", events=ops), NS(name="XLA Modules", events=mods)])


@pytest.fixture()
def made_up():
    ops = [ev("%while.1 = (s32[]) while(...)", 0, 100), ev("%fusion.2 = bf16[8,4]{1,0} fusion(...)", 0, 40),
           ev("%all-reduce.3 = bf16[8]{0} all-reduce(...)", 30, 30), ev("%paged_page_walk.8 = f32[2]{0} custom-call(...)", 70, 30),
           ev("%fusion.2 = bf16[8,4]{1,0} fusion(...)", 200, 50)]
    mods = [ev("jit_decode_block(123)", 0, 100), ev("jit_prefill_and_sample(9)", 200, 50)]
    # as a profiler leaves them: a run cut at each edge, dropped with its ops; the window opens where the first ends
    ops = [ev("%fusion.2 = bf16[8,4]{1,0} fusion(...)", -30, 30)] + ops + [ev("%fusion.2 = bf16[8,4]{1,0} fusion(...)", 300, 20)]
    mods = [ev("jit_decode_block(122)", -30, 30)] + mods + [ev("jit_decode_block(124)", 300, 20)]
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[ev("x", 0, 10**9)])])
    return NS(planes=[host, plane("/device:TPU:0", ops, mods), plane("/device:TPU:1", ops, mods)])


def test_busy_is_a_union_and_loops_are_not_counted_twice(made_up):
    r = tr.reduce_profile(made_up)
    # the loop wraps 0..100, but inside it nothing runs over 60..70: idle time within a program shows
    assert r["devices"] == 2 and r["window_s"] == pytest.approx(250e-9) and r["busy_s"] == pytest.approx(140e-9)
    assert all(not tr.CONTAINERS.match(name) for ops in r["op_intervals"] for _, _, name in ops)
    assert "while.1" not in r["ops"]
    assert r["ops"]["fusion.2_bf16_8_4_"] == pytest.approx(90e-9)
    assert r["gaps"] == {"jit_decode_block-jit_prefill_and_sample": pytest.approx(100e-9)}
    assert tr.runs_of(r, "decode_block") == 1


def test_a_trace_that_holds_no_whole_run_is_no_trace(made_up):
    """Of two program runs the first and the last are the cut ones, and
    nothing is left to reduce; `run.py` then reports no result."""
    for p in made_up.planes[1:]:
        p.lines[1].events[:] = p.lines[1].events[:2]
    assert tr.reduce_profile(made_up) is None


def test_exposed_collective_time_leaves_out_what_compute_covers(made_up):
    r = tr.reduce_profile(made_up)
    # the all-reduce runs 30..60; fusion.2 covers 30..40, and the loop that wraps all of it does not count
    assert tr.exposed_seconds(r, r"all-reduce") == pytest.approx(20e-9)


def test_names_fit_the_contract():
    assert tr.op_name("%fusion.212 = s32[4866048]{0} fusion(...)") == "fusion.212_s32_4866048_"
    assert tr.op_name("%while.96 = (s32[], bf16[3]{0}) while(...)") == "while.96"
    assert tr.module_name("jit_decode_block(8656448964845317181)") == "jit_decode_block"
    assert tr.reduce_profile(NS(planes=[])) is None
