"""Generators: the same seed gives the same plan, every seed replays the
mix's own trace with other contents, another `shape_seed` gives another
order of the same sizes, and a closed loop opens out of phase."""

import pytest

from acpbench import spec
from acpbench.generators import _shapes

import glob
import os

BENCH = spec.benchmark()
# every mix file the benchmark carries
MIXES = sorted(os.path.basename(f)[:-5] for f in glob.glob(os.path.join(spec.TRAFFIC_DIRS[0], "*.json")))
CONFIG = spec.load_json(os.path.join(spec.ROOT, BENCH["configs"][0]["file"]))


def _requests(plan):
    if "requests" in plan:
        return plan["requests"]
    return [r for seq in plan["clients"] for r in seq]


def _size(request):
    return len(request["prompt"])


def _plan(mix_name, seed, seconds=40.0, **changed):
    mix = dict(spec.load_json(os.path.join(spec.TRAFFIC_DIRS[0], mix_name + ".json")), **changed)
    return mix, spec.generator(mix["kind"]).plan(mix, seed, seconds, CONFIG)


def test_every_cells_mix_is_covered_here():
    assert {w["traffic"] for w in BENCH["workloads"]} <= set(MIXES)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_plan(mix):
    assert _plan(mix, 2**31 + 17)[1] == _plan(mix, 2**31 + 17)[1]


def _clock_sees(plan):
    """What of a plan the clock can see: sizes, order, due times."""
    return [(_size(r), r.get("max_tokens"), r.get("due_s")) for r in _requests(plan)]


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_replays_the_same_trace_with_other_contents(mix):
    a, b = _plan(mix, 1)[1], _plan(mix, 2**31 + 2)[1]
    assert _clock_sees(a) == _clock_sees(b)
    contents = lambda p: [r["prompt"] for r in _requests(p)]  # noqa: E731
    assert contents(a) != contents(b)


@pytest.mark.parametrize("mix", MIXES)
def test_another_shape_seed_orders_the_same_sizes_otherwise(mix):
    params, a = _plan(mix, 1)
    b = _plan(mix, 1, shape_seed=params["shape_seed"] + 1)[1]
    sizes = lambda p: sorted(_size(r) for r in _requests(p))  # noqa: E731
    assert sizes(a) == sizes(b)
    assert [_size(r) for r in _requests(a)] != [_size(r) for r in _requests(b)]
    if a["mode"] == "open":
        assert len(a["requests"]) == len(b["requests"])
        assert sorted(r["max_tokens"] for r in a["requests"]) == sorted(r["max_tokens"] for r in b["requests"])


@pytest.mark.parametrize("mix", MIXES)
def test_sizes_stay_inside_what_the_mix_states(mix):
    params, plan = _plan(mix, 5)
    for r in _requests(plan):
        assert params["prompt_tokens"]["min"] <= len(r["prompt"]) <= params["prompt_tokens"]["max"]
        assert 2 <= r["max_tokens"] <= params["answer_tokens"]["max"]
        assert all(0 <= t < params["prompt_vocab"] for t in r["prompt"][:8])


@pytest.mark.parametrize("mix", [m for m in MIXES if _plan(m, 0)[1]["mode"] == "open"])
def test_open_loop_keeps_its_rate_and_order(mix):
    params, plan = _plan(mix, 9, seconds=40.0)
    due = [r["due_s"] for r in plan["requests"]]
    assert due == sorted(due) and due[-1] < params["ramp_s"] + 40.0
    assert abs(len(due) / (params["ramp_s"] + 40.0) - params["rate_per_s"]) < 0.1 * params["rate_per_s"]


@pytest.mark.parametrize("mix", [m for m in MIXES if _plan(m, 0)[1]["mode"] == "closed"])
def test_closed_loop_opens_out_of_phase(mix):
    params, plan = _plan(mix, 11)
    assert len(plan["clients"]) == params["clients"]
    firsts = sorted(seq[0]["max_tokens"] for seq in plan["clients"])
    # first answers end spread over the answers' range, not together
    assert firsts[0] < 0.2 * params["answer_tokens"]["max"] and firsts[-1] > 0.5 * params["answer_tokens"]["min"]
    assert len(set(firsts)) > 0.7 * len(firsts)
    later = [r["max_tokens"] for seq in plan["clients"] for r in seq[1:]]
    assert min(later) >= params["answer_tokens"]["min"]


def test_shapes_are_fixed_multisets():
    assert _shapes.lognormal(5, 100, 0.5, 10, 1000) == _shapes.lognormal(5, 100, 0.5, 10, 1000)
    gaps = _shapes.exponential_gaps(200, 4.0)
    assert abs(sum(gaps) - 50.0) < 1e-9 and min(gaps) > 0
    assert _shapes.uniform(4, 0, 8) == [1, 3, 5, 7]
    with pytest.raises(ValueError):
        _shapes.sizes({"dist": "zipf"}, 3)
