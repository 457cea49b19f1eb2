"""The program's spans against the device's idle time: on made-up planes
for the arithmetic (attribution sums to the idle exactly, the innermost
span wins, uncovered idle is unnamed, a skewed clock is seen and said),
and on a small trace recorded on a v5e of the program's tiny engine with
its spans (`python -m acpbench.record_spans`)."""

import os
from types import SimpleNamespace as NS

import pytest

from acpbench import host_spans as hs
from acpbench import trace_reduce as tr

SMALL = os.path.join(os.path.dirname(__file__), "data", "small_spans.xplane.pb")
MS = 1_000_000


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=list(stats.items()))


def device(index, runs, skew=0):
    """One op and one module event a run: (start, duration, module). As
    in a profiler's trace, a run cut at either edge comes before and after
    them; the reduction drops both, and its window opens where the first
    ends: here, at the first whole run's start."""
    cut = "jit_cut_at_an_edge"
    runs = [(runs[0][0] - MS, MS, cut)] + list(runs) + [(runs[-1][0] + runs[-1][1] + MS, MS, cut)]
    ops = [ev("%fusion.1 = bf16[8]{0} fusion(...)", s + skew, d) for s, d, _ in runs]
    mods = [ev(f"{m}(7)", s + skew, d) for s, d, m in runs]
    return NS(name=f"/device:TPU:{index}", lines=[NS(name="XLA Ops", events=ops), NS(name="XLA Modules", events=mods)])


def block(cycle, t, prefill=False):
    """The host's spans of one serial cycle starting at t (ms): admit 1,
    launch 2 (its jitted call begins 0.5 ms into it, the device starts 1 ms
    into it), the device runs 10, fetch returns 0.5 after it, commit 1.5,
    publish 1."""
    t *= MS
    key = "prefill[paged,64x1]" if prefill else "decode[paged,8x8]"
    return [
        ev("acp.cycle", t, 17 * MS, step_num=cycle, _r=1),
        ev("acp.admit", t, 1 * MS, cycle=cycle),
        ev("acp.launch", t + 1 * MS, 2 * MS, cycle=cycle, program=key, call_us=500),
        ev("acp.fetch", t + 3 * MS, 9 * MS + MS // 2, cycle=cycle),
        ev("acp.commit", t + 12 * MS + MS // 2, MS + MS // 2, cycle=cycle),
        ev("acp.publish", t + 14 * MS, 1 * MS, cycle=cycle),
    ]


def profile(skew=0, chips=1, cycles=6):
    host, runs = [ev("some other TraceMe", 0, 5)], []
    for c in range(cycles):
        t = 20 * c
        prefill = c == 2
        host += block(c + 1, t, prefill)
        runs.append(((t + 2) * MS, 10 * MS, "jit_prefill_and_sample" if prefill else "jit_decode_block"))
    planes = [NS(name="/host:CPU", lines=[NS(name="python3", events=host)])]
    planes += [device(i, runs, skew) for i in range(chips)]
    return NS(planes=planes)


def test_spans_are_read_from_the_host_plane_alone():
    spans = hs.read_profile(profile())
    assert len(spans) == 36 and spans == sorted(spans)
    assert {s[2] for s in spans} == {"cycle", "admit", "launch", "fetch", "commit", "publish"}
    first = [s for s in spans if s[3] == 1]
    assert len(first) == 6
    launch = next(s for s in first if s.name == "launch")
    assert launch[:5] == (1 * MS, 3 * MS, "launch", 1, "decode[paged,8x8]")
    assert launch.call_ns == 1 * MS + 500_000
    cycle = next(s for s in first if s.name == "cycle")
    assert cycle[:2] == (0, 17 * MS) and cycle.call_ns is None and cycle.program is None
    assert hs.read_profile(NS(planes=[device(0, [(0, 5, "jit_decode_block")])])) == []


@pytest.mark.parametrize("chips", [1, 4])
def test_attribution_sums_to_the_idle_time_exactly(chips):
    p = profile(chips=chips)
    reduced = tr.reduce_profile(p)
    found = hs.analyse_profile(p, reduced)
    idle = reduced["window_s"] - reduced["busy_s"]
    assert idle == pytest.approx(50e-3)  # 5 gaps of 10 ms between 6 runs of 10
    assert found["idle_s"] == pytest.approx(idle, rel=1e-12)
    assert sum(found["by_phase"].values()) == pytest.approx(idle, rel=1e-12)
    # a gap: fetch 0.5, commit 1.5, publish 1, the cycle's tail 2, nothing 3, the next cycle's admit 1, launch 1
    by = {k: round(v * 1e3, 6) for k, v in found["by_phase"].items()}
    assert by == {"fetch": 2.5, "commit": 7.5, "publish": 5.0, "cycle": 10.0, "unnamed": 15.0,
                  "admit": 5.0, "launch": 5.0}
    assert found["blocks"] == 5 and found["spans"] == 36


def test_the_innermost_span_wins_and_the_cycle_names_only_what_no_phase_does():
    spans = [hs.Span(0, 100, "cycle", 1), hs.Span(10, 90, "admit", 1),
             hs.Span(20, 60, "launch", 1, "decode[paged,8x8]", 25), hs.Span(30, 40, "fetch", 1)]
    assert hs.innermost(spans) == [(0, 10, "cycle"), (10, 20, "admit"), (20, 30, "launch"), (30, 40, "fetch"),
                                   (40, 60, "launch"), (60, 90, "admit"), (90, 100, "cycle")]
    # a cycle that opens inside a phase (a request arrives while the loop is parked) does not take it over
    late = [hs.Span(0, 50, "admit", 0), hs.Span(20, 100, "cycle", 1)]
    assert hs.innermost(late) == [(0, 50, "admit"), (50, 100, "cycle")]
    ops = [[(0, 15, "a"), (35, 55, "b"), (95, 120, "c")]]
    got = hs.attribute(ops, spans)
    assert {k: round(v * 1e9) for k, v in got["by_phase"].items()} == {
        "admit": 5 + 30, "launch": 10 + 5, "fetch": 5, "cycle": 5, "unnamed": 0}
    assert got["idle_s"] * 1e9 == pytest.approx(60)


def test_idle_that_no_span_covers_is_unnamed():
    ops = [[(0, 10, "a"), (50, 60, "b")], [(0, 10, "a"), (30, 60, "b")]]
    got = hs.attribute(ops, [hs.Span(20, 25, "commit", 1)])
    # chip 0 idles 10..50 (5 of it under commit), chip 1 idles 10..30 (5 under commit): means over chips
    assert got["by_phase"] == {"commit": pytest.approx(5e-9), "unnamed": pytest.approx(25e-9)}
    assert got["idle_s"] == pytest.approx(30e-9)
    none = hs.attribute(ops, [])
    assert none["by_phase"] == {"unnamed": pytest.approx(30e-9)}


def test_one_clock_is_checked_block_by_block():
    p = profile()
    a = hs.alignment(hs.read_profile(p), hs.device_runs(p))
    assert a["runs"] == 5 and a["blocks"] == 5  # the prefill is no decode block, on either side
    assert a["share"] == a["share_uncorrected"] == 1.0 and not a["corrected"] and a["offset_ms"] == 0.0
    # the call begins at +1.5 ms and the device at +2; the fetch ends half a millisecond after the device
    assert a["bounds_ms"] == (pytest.approx(-0.5), pytest.approx(0.5))
    assert a["latency_ms"] == pytest.approx(0.5)


def test_a_block_launched_before_the_trace_began_is_left_out_of_the_check():
    p = profile()
    host = p.planes[0].lines[0]
    host.events = [e for e in host.events if dict(e.stats).get("cycle", dict(e.stats).get("step_num")) != 1]
    a = hs.alignment(hs.read_profile(p), hs.device_runs(p))
    assert a["runs"] == 5 and a["blocks"] == 4 and a["share"] == 1.0 and not a["corrected"]


@pytest.mark.parametrize("skew_ms", [-4, -2, 3])
def test_a_skewed_clock_is_reported_and_corrected(skew_ms):
    """The device planes `skew_ms` ahead of the host plane, by less than
    half a block (10 ms here; on the chip 1-3 ms of a hundred or more): the
    bounds leave 0 out, the correction is their middle, and the attribution
    is what it is with no skew."""
    p = profile(skew=skew_ms * MS)
    reduced = tr.reduce_profile(p)
    raw = hs.alignment(hs.read_profile(p), hs.device_runs(p))
    assert raw["corrected"] and raw["share_uncorrected"] == 0.0 and raw["share"] == 1.0 and raw["failed"] is None
    assert raw["bounds_ms"] == (pytest.approx(-skew_ms - 0.5), pytest.approx(-skew_ms + 0.5))
    assert raw["offset_ms"] == pytest.approx(-skew_ms)
    found = hs.analyse_profile(p, reduced)
    text = hs.line(found, reduced)
    assert "CORRECTED by" in text and "100.0% of them" in text and "(0.0% held before)" in text
    straight = hs.analyse_profile(profile(), tr.reduce_profile(profile()))
    assert found["idle_s"] == pytest.approx(straight["idle_s"])
    for name, seconds in straight["by_phase"].items():
        assert found["by_phase"][name] == pytest.approx(seconds, abs=1e-9), name
    assert "no correction" in hs.line(straight, reduced)


@pytest.mark.parametrize("skew_ms", [-150, 37, 5000])
def test_an_offset_of_blocks_is_a_failed_alignment_and_corrects_nothing(skew_ms):
    """No profiler sets the planes whole blocks apart: such a reading is a
    pairing gone wrong (PR 31's warm tp=4 runs: 752 ms, two blocks). The
    device's runs then find no window of their own, or no one offset holds
    for the pairs they find; the `[spans]` line says that the alignment
    failed, and the planes are read as they are: every reader still gives
    its number."""
    p = profile(skew=skew_ms * MS)
    reduced = tr.reduce_profile(p)
    found = hs.analyse_profile(p, reduced)
    a = found["align"]
    assert a["failed"] and not a["corrected"] and a["offset_ms"] == 0.0
    text = hs.line(found, reduced)
    assert "ALIGNMENT FAILED" in text and "CORRECTED" not in text
    as_they_are = hs.attribute(reduced["op_intervals"], hs.read_profile(p), 0, reduced["windows"])
    assert found["by_phase"] == as_they_are["by_phase"] and found["idle_s"] == pytest.approx(50e-3)
    run = NS(trace=reduced, host_spans=found)
    assert hs.idle_named_share(run) is not None and hs.idle_ms_per_block(run, "launch") is not None


# A warm traced `q32b-tp4-decode` run as the chip recorded it (my chip run, PR 32, call 1, `warm1`; ms from the
# slice's first whole run, chip 0): launch start, jitted call and the end of the fetch after it; the device's runs.
# A prefill came between the second block and the third, whose launch prepared for 8.5 ms before its call.
TP4_WINDOWS = [(3.6, 4.6, 344.2), (344.9, 345.9, 685.7), (747.1, 755.6, 1094.4), (1095.1, 1096.0, 1435.5)]
TP4_RUNS = [(4.8, 336.8), (345.8, 337.3), (755.7, 336.6), (1096.1, 337.3)]


def tp4_profile(windows, device_runs=TP4_RUNS):
    host = []
    for c, (launch, call, fetched) in enumerate(windows):
        host += [ev("acp.cycle", int(launch * MS), int((fetched - launch + 5) * MS), step_num=c + 1),
                 ev("acp.launch", int(launch * MS), int((call - launch + 0.3) * MS), cycle=c + 1,
                    program="decode[paged,24x8]", call_us=int(round((call - launch) * 1000))),
                 ev("acp.fetch", int((call + 0.3) * MS), int((fetched - call - 0.3) * MS), cycle=c + 1),
                 ev("acp.commit", int(fetched * MS), int(0.4 * MS), cycle=c + 1)]
    runs = [(int(s * MS), int(d * MS), "jit_decode_block") for s, d in device_runs]
    return NS(planes=[NS(name="/host:CPU", lines=[NS(name="python3", events=host)]), device(0, runs)])


@pytest.mark.parametrize("kept,paired,failed", [((0, 1, 2, 3), 4, False), ((2, 3), 2, False), ((0, 3), 2, False),
                                                ((1,), 1, True)],
                         ids=["all-four", "the-last-two", "the-outer-two", "one-of-four"])
def test_four_blocks_of_a_short_slice_are_paired_by_where_they_lie(kept, paired, failed):
    """Four device runs and the host's spans for all or some of them. The
    parent's pairing tried the two sequences a few blocks out of step
    either way and kept the step under which fetch end less device end
    agreed best from block to block. With all four spans there it paired
    the host's last two with the device's first two (they agree to 0.2 ms,
    the true four to 0.25), read "2 of the device's 4 runs have their launch
    and fetch in the trace" and "corrected" the planes by +751.6 ms, two
    blocks: on the chip, and on this copy of that run (shown by hand with
    the parent's file). With spans for two of the four the same rule chose
    between two steps of two pairs each, by a tie. A run now goes with the
    window nearest it that is nearest to it in turn: every span present is
    paired with its own run, the offset is the planes' own millisecond or
    two, and with spans for one run of four the alignment fails and
    corrects nothing."""
    p = tp4_profile([TP4_WINDOWS[k] for k in kept])
    reduced = tr.reduce_profile(p)
    found = hs.analyse_profile(p, reduced)
    a = found["align"]
    assert (a["runs"], a["windows"], a["blocks"]) == (4, len(kept), paired) and bool(a["failed"]) is failed
    assert abs(a["offset_ms"]) < 3 and not (failed and a["corrected"])
    if not failed:
        assert a["share"] == 1.0 and -0.2 < a["bounds_ms"][0] < a["bounds_ms"][1] < 3
    assert ("ALIGNMENT FAILED" in hs.line(found, reduced)) is failed
    assert found["idle_s"] == pytest.approx(reduced["window_s"] - reduced["busy_s"])


def test_an_offset_longer_than_a_block_is_not_credible():
    """Blocks far apart (a loop that parks between them): each run still
    finds the window nearest it, and one offset holds for all three, but it
    is longer than a block from launch to fetch, so the run as well belongs
    to no window at all: no correction."""
    windows = [(100.0 * k, 100.0 * k + 1, 100.0 * k + 12) for k in range(3)]
    p = tp4_profile(windows, [(100.0 * k + 31.5, 10.0) for k in range(3)])
    a = hs.alignment(hs.read_profile(p), hs.device_runs(p))
    assert a["blocks"] == a["runs"] == 3 and a["bounds_ms"] == (pytest.approx(-30.5), pytest.approx(-29.5))
    assert "longer than a block" in a["failed"] and not a["corrected"] and a["offset_ms"] == 0.0 and a["share"] == 0.0


def test_a_skew_inside_the_bounds_cannot_be_told_from_none():
    """Half a millisecond either way is what the spans leave open: the line
    prints the bounds, so a reader sees how far launch and fetch may trade."""
    p = profile(skew=300_000)
    a = hs.alignment(hs.read_profile(p), hs.device_runs(p))
    assert not a["corrected"] and a["share"] == 1.0
    assert a["bounds_ms"] == (pytest.approx(-0.8), pytest.approx(0.2))


def test_without_spans_every_reader_says_nothing(tmp_path, monkeypatch):
    """The parent commit opens no span: the trace is read, the line says so,
    and no metric is reported."""
    p = NS(planes=[NS(name="/host:CPU", lines=[NS(name="python3", events=[ev("x", 0, 5)])]),
                   device(0, [(0, 10 * MS, "jit_decode_block"), (20 * MS, 10 * MS, "jit_decode_block")])])
    reduced = tr.reduce_profile(p)
    assert hs.analyse_profile(p, reduced) is None
    assert "opens none" in hs.line(None, reduced)
    run = NS(trace=None, cell={"workload": {"name": "q7b-decode-saturated"}})
    assert hs.analyse(run) is None and hs.idle_named_share(run) is None
    assert hs.idle_ms_per_block(run, "launch") is None
    run = NS(trace=reduced, cell={"workload": {"name": "no-such-cell"}}, host_spans=None)
    assert hs.idle_named_share(run) is None and hs.idle_ms_per_block(run, "admit") is None


def test_the_metrics_are_per_decode_block_and_a_share_of_the_idle():
    p = profile()
    reduced = tr.reduce_profile(p)
    run = NS(trace=reduced, host_spans=hs.analyse_profile(p, reduced))
    assert hs.idle_ms_per_block(run, "commit") == pytest.approx(7.5 / 5)
    assert hs.idle_ms_per_block(run, "park") == 0.0
    # neither the 15 ms no span covers nor the 10 ms only the cycle covers are named
    assert hs.idle_named_share(run) == pytest.approx(100 * (50 - 15 - 10) / 50)


def test_counters_are_read_from_the_windows_edges():
    from acpbench.layer_metrics import host_ms_per_block, queue_wait_ms_mean

    def snap(blocks, launch, fetch, park, qs, qn):
        return {"perf": {"blocks": blocks, "phases": {"launch": {"s": launch, "n": 1}, "fetch": {"s": fetch, "n": 1},
                                                      "park": {"s": park, "n": 1}}},
                "scheduler": {"queue_wait": {"s": qs, "n": qn}}}

    run = NS(trace=None, stats={"open": snap(10, 1.0, 5.0, 2.0, 3.0, 10), "close": snap(30, 1.5, 9.0, 2.5, 9.0, 40)})
    run.stats["close"]["perf"]["phases"]["commit"] = {"s": 0.25, "n": 3}  # a phase first seen inside the window
    assert host_ms_per_block.read(run) == pytest.approx((0.5 + 0.25) * 1e3 / 20)
    assert queue_wait_ms_mean.read(run) == pytest.approx(6.0 * 1e3 / 30)
    parent = NS(trace=None, stats={"open": {"perf": {}, "scheduler": {}}, "close": {"perf": {}, "scheduler": {}}})
    assert host_ms_per_block.read(parent) is None and queue_wait_ms_mean.read(parent) is None
    idle = NS(trace=None, stats={"open": snap(10, 1.0, 5.0, 2.0, 3.0, 10), "close": snap(10, 1.0, 5.0, 2.0, 3.0, 10)})
    assert host_ms_per_block.read(idle) is None and queue_wait_ms_mean.read(idle) is None


# -- the recorded trace: the program's tiny engine on a v5e, with its spans ----


@pytest.fixture(scope="module")
def recorded():
    import jax

    profile = jax.profiler.ProfileData.from_file(SMALL)
    return profile, tr.reduce_profile(profile)


def test_recorded_trace_holds_the_engines_spans_beside_the_device_plane(recorded):
    profile, reduced = recorded
    spans = hs.read_profile(profile)
    assert reduced["devices"] == 1
    assert {s.name for s in spans} >= {"cycle", "admit", "launch", "fetch", "commit", "publish"}
    cycles = [s for s in spans if s.name == "cycle"]
    assert len(cycles) >= 3 and [s.cycle for s in cycles] == sorted({s.cycle for s in cycles})
    for c in cycles:  # every phase a cycle holds carries the cycle's number
        inside = [s for s in spans if s.name != "cycle" and c.start_ns <= s.start_ns and s.end_ns <= c.end_ns]
        assert inside and all(s.cycle == c.cycle for s in inside)
    launches = [s for s in spans if s.name == "launch" and s.program]
    assert {s.program.split("[")[0] for s in launches} >= {"decode", "prefill_cont"}
    assert all(s.start_ns <= s.call_ns <= s.end_ns for s in launches)
    # the device ran a decode block a launch of one
    assert len(hs.device_runs(profile)) == sum(1 for s in launches if s.program.startswith("decode[")) == 3


def test_recorded_trace_attributes_every_idle_nanosecond(recorded):
    profile, reduced = recorded
    found = hs.analyse_profile(profile, reduced)
    idle = reduced["window_s"] - reduced["busy_s"]
    assert found["idle_s"] == pytest.approx(idle, rel=1e-9)
    assert sum(found["by_phase"].values()) == pytest.approx(idle, rel=1e-9)
    named = sum(v for k, v in found["by_phase"].items() if k not in ("unnamed", "cycle"))
    assert named / idle > 0.95  # the loop is serial: the chip idles while the host launches, fetches, commits
    assert found["by_phase"]["launch"] > found["by_phase"]["fetch"] > found["by_phase"]["commit"] > 0
    assert found["blocks"] == 2  # whole runs: the trace's first decode block is dropped


def test_recorded_trace_shows_the_device_planes_lagging_the_host_plane(recorded):
    """On the chip the two planes are NOT on one clock: as recorded, every
    decode block starts on the device before its jitted call began. The
    bounds leave 0 out, the reader corrects by their middle and says so."""
    profile, reduced = recorded
    a = hs.analyse_profile(profile, reduced)["align"]
    lo, hi = a["bounds_ms"]
    assert 0 < lo < hi < 5
    assert a["corrected"] and a["offset_ms"] == pytest.approx((lo + hi) / 2, abs=1e-6)
    assert a["share_uncorrected"] == 0.0 and a["share"] == 1.0 and a["blocks"] == a["runs"] == 2
    assert 0 < a["latency_ms"] < hi - lo
    assert "CORRECTED by" in hs.line(hs.analyse_profile(profile, reduced), reduced)
