"""The gap per request, percentiles with failures as missing, counting."""

import math

import pytest

from acpbench import loadgen, metrics, peaks
from acpbench.end_to_end import gap_p50_ms, tokens_per_s_per_chip
from acpbench.layer_metrics import ttft_p90_ms_recorded as ttft_p90_ms
from acpbench.kernels import page_walk
from acpbench.run import Run, count_requests


def rec(due, first=None, last=None, n=0, end=None, error=None, prompt=10, blocks=None, censored=False):
    r = loadgen.Record(idx=0, due=due, prompt_len=prompt, max_tokens=n)
    r.first_t, r.last_t, r.n_tokens, r.end_t, r.error, r.censored = first, last, n, end, error, censored
    r.blocks = blocks or []
    return r


def test_gap_is_the_request_mean_not_a_block_cadence():
    assert metrics.request_gap_ms(rec(0, first=1.0, last=2.0, n=11)) == pytest.approx(100.0)
    assert metrics.request_gap_ms(rec(0, first=1.0, last=1.0, n=1)) is None
    assert metrics.request_gap_ms(rec(0, first=1.0, last=2.0, n=11, error="shed")) is None


@pytest.mark.parametrize("values,q,missing,want", [
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90, 0, 9),
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 50, 0, 5),
    ([1, 2, 3, 4, 5, 6, 7, 8, 9], 90, 1, 9),
    ([1, 2, 3, 4, 5, 6, 7, 8], 90, 2, math.inf),
    ([], 50, 0, None),
    ([7.0], 50, 0, 7.0),
])
def test_percentile_counts_failures_as_beyond_any_value(values, q, missing, want):
    assert metrics.percentile(values, q, missing=missing) == want


def test_distribution_uses_the_contracts_quartiles():
    d = metrics.distribution([1.0, 2.0, 3.0, 4.0, 5.0, 6.0])
    assert (d["n"], d["min"], d["max"]) == (6, 1.0, 6.0) and d["q1"] == 1.75 and d["q3"] == 5.25
    assert metrics.distribution([]) == {"n": 0}


def _run(records, mode="open"):
    return Run(records=records, window=(10.0, 50.0), seconds=40.0, chips=2, mode=mode,
               mix={"drain_limit_s": 60})


def test_ttft_p90_counts_the_failed_as_missing():
    ok = [rec(10 + i, first=10 + i + 0.1 * (i + 1), end=30) for i in range(8)]
    failed = [rec(20, error="EngineOverloadedError"), rec(21, error="not ended")]
    outside = [rec(5, first=9), rec(55, first=56)]
    assert ttft_p90_ms.read(_run(ok + outside)) == pytest.approx(800.0)
    assert ttft_p90_ms.read(_run(ok + failed + outside)) == 60000.0


def test_gap_p50_reads_requests_ended_in_the_window():
    rs = [rec(0, first=11, last=12, n=11, end=12), rec(0, first=11, last=13, n=11, end=13),
          rec(0, first=11, last=14, n=11, end=14), rec(0, first=1, last=2, n=3, end=2),
          rec(0, first=11, last=15, n=11, end=15, censored=True)]
    assert gap_p50_ms.read(_run(rs)) == pytest.approx(200.0)


def test_tokens_per_s_per_chip_is_all_tokens_over_all_time():
    # window 10..50; an emission stands for tokens produced since the one before (or since the send)
    a = rec(8.0, blocks=[(9.0, 8), (11.0, 8), (49.0, 8), (51.0, 8)])  # 9..11 and 49..51 lie half inside
    b = rec(20.0, blocks=[(30.0, 64), (30.0, 2)])  # sent at 20: all inside; a second stamp at the same instant
    a.sent, b.sent = 8.0, 20.0
    assert metrics.tokens_in_window([a, b], (10.0, 50.0)) == pytest.approx(4 + 8 + 4 + 64 + 2)
    assert tokens_per_s_per_chip.read(_run([a, b])) == pytest.approx(82 / 40.0 / 2)


@pytest.mark.parametrize("shift", [0.0, 0.1, 0.2, 0.3])
def test_the_token_count_does_not_jump_with_the_windows_edge(shift):
    """32 slots handing over 8 tokens every 0.4 s: wherever the window's
    edges fall between two hand-overs, 40 s hold 40 / 0.4 blocks."""
    rs = []
    for slot in range(32):
        r = rec(0.0, blocks=[(0.4 * (i + 1), 8) for i in range(200)])
        r.sent = 0.0
        rs.append(r)
    assert metrics.tokens_in_window(rs, (10.0 + shift, 50.0 + shift)) == pytest.approx(32 * 8 * 100)


def test_attempted_and_failed():
    open_records = [rec(12, end=20), rec(13, error="shed"), rec(5, end=11), rec(14, end=70)]
    assert count_requests(_run(open_records, "open")) == (3, 1)
    closed = [rec(2, end=12), rec(12, end=30, error="boom"), rec(40, end=None, censored=True), rec(1, end=5)]
    assert count_requests(_run(closed, "closed")) == (2, 1)


def test_peaks_refuse_an_unknown_device():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for kind in ("TPU v9", "cpu", "_source"):
        with pytest.raises(KeyError):
            peaks.peaks(kind)


def test_page_walk_counts_live_pages_only():
    geometry = dict(page_size=16, kv_heads=4, head_dim=128, n_layers=28)
    one_page = 16 * 4 * 128 * 2 * 2 * 28
    assert page_walk.bytes_per_step([1], **geometry) == one_page
    assert page_walk.bytes_per_step([16, 17, 0], **geometry) == 3 * one_page
    assert page_walk.flops_per_step([10], heads=28, head_dim=128, n_layers=28) == 4 * 10 * 28 * 128 * 28


def test_cycle_intervals_join_the_stamps_of_one_hand_over():
    from acpbench.loadgen import Record

    a, b = Record(0, 0.0, 4, 8), Record(1, 0.0, 4, 8)
    a.blocks = [(1.000, 8), (1.400, 8), (1.900, 8)]
    b.blocks = [(1.001, 8), (1.401, 8), (2.500, 8)]  # the last one lies outside the window
    got = metrics.cycle_intervals_ms([a, b], (0.5, 2.0))
    assert [round(g) for g in got] == [400, 500]
    assert metrics.cycle_intervals_ms([], (0.0, 1.0)) == []
